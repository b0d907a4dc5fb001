"""Share of the SpMV's bandwidth bound, in % (layer: SpMV). Moves
``solve_s``.

Bytes per SpMV call and chip are those of the CSR the user hands in, at
the solve's working dtype ``w``, over the rows and nonzeros the chip owns
in equal row blocks: ``nnz*(w+4) + 2*n*w + (n+1)*4`` (values and column
indices, ``x`` read and ``y`` written once, row pointers). ELL padding is
not counted: it depends on the implementation. The bound is those bytes
over the HBM bandwidth of ``peaks.json``; the share is the bound over the
device time of the SpMV's ops (``spmv_ms_per_iter``'s rule), on the device
with the most SpMV time. A change of the stored value width or of the
indices needs a benchmark change of this count.
"""

from chipbench.metrics.spmv_ms_per_iter import is_spmv


def csr_bytes(rows: int, nnz: int, itemsize: int) -> int:
    return nnz * (itemsize + 4) + 2 * rows * itemsize + (rows + 1) * 4


def chip_bytes(problem) -> list[int]:
    return [csr_bytes(rows, nnz, problem.itemsize)
            for rows, nnz in problem.row_blocks()]


def read(ctx):
    if ctx.trace is None or ctx.spmv_calls == 0:
        return None
    per_dev = ctx.trace.time_ns(is_spmv)
    dev = max(per_dev, key=per_dev.get)
    if per_dev[dev] <= 0:
        return None
    blocks = chip_bytes(ctx.problem)
    nbytes = blocks[dev] if len(blocks) == len(per_dev) else max(blocks)
    bound_s = ctx.spmv_calls * nbytes / float(ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * bound_s / (per_dev[dev] / 1e9)
