"""The program's layer scopes, as a device op's ``op`` names them.

``energy/trace.region(name)`` in the program opens a ``jax.named_scope``
of the same name, so every op traced inside a region carries the name as
one step of its ``op_name``: ``jit(solve)/while/body/spmv/interior/gather``,
``jit(solve)/shard_map/while/body/spmv/overlap/halo/ppermute``,
``jit(solve)/while/body/reductions/psum``. The region names are
:data:`LAYERS`; ``core/spmv.spmv_shard`` adds ``interior``, ``boundary``
and ``halo`` inside the SpMV. A program without these scopes (one older
than them) gives no op a step of :data:`LAYERS`, and the readers built on
this module then find nothing and stay silent.
"""

from __future__ import annotations

import re

from chipbench.metrics.spmv_roofline import chip_bytes

LAYERS = ("spmv", "halo", "overlap", "reductions", "precond", "vcycle")

# an op fused from several JAX ops names them all, joined by ";"
_SEP = re.compile(r"[/;]")


def steps(ev) -> set[str]:
    return set(_SEP.split(ev.op)) if ev.op else set()


def in_scope(name: str):
    """An event matcher: the op was traced inside scope ``name``."""
    return lambda ev: name in steps(ev)


def unscoped(ev) -> bool:
    """The op has no layer scope: the loop's condition and counter, the
    compiler's own copies, slicing of the arguments."""
    return steps(ev).isdisjoint(LAYERS)


def ms_per_iter(ctx, match):
    """Device milliseconds per CG iteration in the ops ``match`` accepts,
    on the busiest device; None where it finds none."""
    if ctx.trace is None or ctx.iterations == 0:
        return None
    busiest = max(ctx.trace.time_ns(match).values())
    if busiest <= 0:
        return None
    return busiest / 1e6 / ctx.iterations


def roofline_share(ctx, match):
    """Share of the SpMV's bandwidth bound, in %, over the device time of
    the ops ``match`` accepts, on the device with the most of it. The
    bytes per SpMV call and chip are ``spmv_roofline.chip_bytes``'s count
    of the user's CSR; None where no op matches."""
    if ctx.trace is None or ctx.spmv_calls == 0:
        return None
    per_dev = ctx.trace.time_ns(match)
    dev = max(per_dev, key=per_dev.get)
    if per_dev[dev] <= 0:
        return None
    blocks = chip_bytes(ctx.problem)
    nbytes = blocks[dev] if len(blocks) == len(per_dev) else max(blocks)
    bound_s = ctx.spmv_calls * nbytes / float(ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * bound_s / (per_dev[dev] / 1e9)
