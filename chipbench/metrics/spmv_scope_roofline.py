"""Share of the SpMV's bandwidth bound over the device time of the
program's ``spmv`` scope, in % (layer: SpMV). Moves ``solve_s``.

The bytes are ``spmv_roofline``'s count of the user's CSR per SpMV call
and chip (``spmv_roofline.chip_bytes``); the time is that of the ops
``spmv_scope_ms_per_iter`` reads, on the device with the most of it
(``chipbench/scopes.roofline_share``).
"""

from chipbench import scopes
from chipbench.metrics.spmv_scope_ms_per_iter import is_spmv


def read(ctx):
    return scopes.roofline_share(ctx, is_spmv)
