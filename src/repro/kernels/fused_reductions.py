"""Fused vector-op Pallas kernels: the CG hot path in minimal HBM passes.

CG's per-iteration scalar + vector work reads the same vectors several times
when expressed as separate ops (dots, axpys). The kernels here stream every
operand exactly once per call (chunked grid, SMEM scalar accumulation), so
each call is ONE full-vector HBM sweep:

* ``fused_dots_n``   — N inner products in one pass. Duplicate operands and
  duplicate pairs are deduplicated statically, so e.g. the fcg triple
  [(r,u), (w,u), (r,r)] with u==r reads only {r, w} and multiplies once per
  unique pair.
* ``fused_axpy``     — a*x + y.
* ``fused_axpy2``    — two independent axpys (the p/s and x/r update pairs)
  in one pass.
* ``fused_axpy2_dots`` — the CG update step ``x += a1*p; r -= a1*w`` PLUS
  the follow-up reduction ``r_new . r_new`` in the SAME pass: the freshly
  computed r chunk is still in VMEM when the partial dot accumulates, so the
  re-read of r that a separate dot would cost disappears from HBM traffic.
* ``fused_dots3``    — legacy fixed-arity [p.w, r.r, p.r] wrapper (kept for
  API stability; now handles any length, no shape restriction).

Arbitrary lengths dispatch unconditionally: the grid covers the vector in
lane-aligned chunks and the (possibly ragged) final block is masked inside
the kernel — reductions ignore out-of-range lanes, out-of-range output
writes are clipped by Pallas. No host-side padding copies, so the HBM
traffic really is one read per operand + one write per output. Scalars
(alpha/beta) arrive as a small SMEM operand so traced loop-carried values
work. Accumulation happens in the input dtype, matching the jnp oracles in
``kernels/ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.x32 import pallas_call


# The (n, r) block kernels contract on the MXU, whose default for f32
# operands is a single bf16 pass (about 3 significant digits): too coarse
# for CG's Gram algebra, which then stalls or diverges. Ask for full f32.
_F32 = lax.Precision.HIGHEST


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _require_1d(op: str, *arrays):
    """The fused vector kernels stream (n,) vectors; (n, r) RHS blocks have
    their own one-pass kernels. Fail loudly instead of deep inside the
    masked ragged-block reshape."""
    for a in arrays:
        if a.ndim != 1:
            raise ValueError(
                f"{op} expects 1-D (n,) vectors, got shape {a.shape}; "
                "multi-RHS (n, r) column blocks go through the block "
                "kernels block_gram / block_update / block_update2"
            )


def _chunking(n: int, chunk: int) -> tuple[int, int]:
    """(effective chunk, grid size): lane-aligned, ragged tail allowed."""
    chunk_eff = min(chunk, _round_up(n, 128))
    return chunk_eff, -(-n // chunk_eff)


def _valid_mask(i, chunk: int, n: int):
    """(chunk,) bool mask of in-range lanes for grid step ``i``.

    TPU Mosaic requires >=2-D iota, hence the (1, chunk) detour.
    """
    lane = lax.broadcasted_iota(jnp.int32, (1, chunk), 1).reshape(chunk)
    return (i * chunk + lane) < n


def _valid_rows(i, chunk: int, n: int, r: int):
    """(chunk, r) bool mask of in-range rows of an (n, r) block at grid
    step ``i``. Built 2-D: Mosaic cannot reshape a (chunk,) mask to
    (chunk, 1)."""
    row = lax.broadcasted_iota(jnp.int32, (chunk, r), 0)
    return (i * chunk + row) < n


# ---------------------------------------------------------------------------
# fused_dots_n — N inner products, one pass, deduplicated reads
# ---------------------------------------------------------------------------


def _dedup_pairs(pairs):
    """Static dedup: unique operand arrays, unique (i, j) products, and the
    map from output slot -> unique product."""
    uniq: list = []
    ids: dict[int, int] = {}

    def idx(a):
        if id(a) not in ids:
            ids[id(a)] = len(uniq)
            uniq.append(a)
        return ids[id(a)]

    out_map = []
    prod_ids: dict[tuple[int, int], int] = {}
    prods = []
    for x, y in pairs:
        key = tuple(sorted((idx(x), idx(y))))
        if key not in prod_ids:
            prod_ids[key] = len(prods)
            prods.append(key)
        out_map.append(prod_ids[key])
    return uniq, tuple(prods), tuple(out_map)


def fused_dots_n(pairs, *, chunk: int = 65536, interpret: bool = False) -> jax.Array:
    """Local partial dots for ``pairs = [(x, y), ...]`` — ONE HBM pass.

    Returns a (len(pairs),) vector of LOCAL sums (callers psum once in the
    distributed setting). Operands shared between pairs (by object identity)
    are read once; identical pairs are multiplied once.
    """
    uniq, prods, out_map = _dedup_pairs(pairs)
    _require_1d("fused_dots_n", *uniq)
    k = len(prods)
    (n,) = uniq[0].shape
    dt = uniq[0].dtype
    chunk_eff, grid = _chunking(n, chunk)
    spec = pl.BlockSpec((chunk_eff,), lambda i: (i,))

    def kernel(*refs):
        out_ref = refs[-1]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            for j in range(k):
                out_ref[j] = jnp.zeros((), out_ref.dtype)

        valid = _valid_mask(i, chunk_eff, n)
        vals = [refs[t][...] for t in range(len(uniq))]
        zero = jnp.zeros((), dt)
        for j, (a, b) in enumerate(prods):
            out_ref[j] += jnp.sum(jnp.where(valid, vals[a] * vals[b], zero))

    partials = pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[spec] * len(uniq),
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((k,), dt),
        interpret=interpret,
    )(*uniq)
    if out_map == tuple(range(len(pairs))) and k == len(pairs):
        return partials
    return partials[jnp.asarray(out_map, jnp.int32)]


# ---------------------------------------------------------------------------
# fused axpy family
# ---------------------------------------------------------------------------


def _axpy_kernel(a_ref, x_ref, y_ref, o_ref):
    o_ref[...] = a_ref[0] * x_ref[...] + y_ref[...]


def fused_axpy(a, x, y, *, chunk: int = 65536, interpret: bool = False):
    """a*x + y in one pass; ``a`` may be a traced scalar."""
    _require_1d("fused_axpy", x, y)
    (n,) = x.shape
    chunk_eff, grid = _chunking(n, chunk)
    spec = pl.BlockSpec((chunk_eff,), lambda i: (i,))
    av = jnp.asarray(a, x.dtype).reshape(1)
    return pallas_call(
        _axpy_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=interpret,
    )(av, x, y)


def _axpy2_kernel(a_ref, x1_ref, y1_ref, x2_ref, y2_ref, o1_ref, o2_ref):
    o1_ref[...] = a_ref[0] * x1_ref[...] + y1_ref[...]
    o2_ref[...] = a_ref[1] * x2_ref[...] + y2_ref[...]


def fused_axpy2(a1, x1, y1, a2, x2, y2, *, chunk: int = 65536,
                interpret: bool = False):
    """(a1*x1 + y1, a2*x2 + y2) in one pass over all four vectors."""
    _require_1d("fused_axpy2", x1, y1, x2, y2)
    (n,) = x1.shape
    chunk_eff, grid = _chunking(n, chunk)
    spec = pl.BlockSpec((chunk_eff,), lambda i: (i,))
    av = jnp.stack([jnp.asarray(a1, x1.dtype), jnp.asarray(a2, x1.dtype)])
    return pallas_call(
        _axpy2_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * 4,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n,), x1.dtype)] * 2,
        interpret=interpret,
    )(av, x1, y1, x2, y2)


def fused_axpy2_dots(a1, x1, y1, a2, x2, y2, *, chunk: int = 65536,
                     interpret: bool = False):
    """CG update + follow-up reduction in ONE pass.

    Returns (o1, o2, d) with o1 = a1*x1 + y1, o2 = a2*x2 + y2 and
    d = (1,) LOCAL partial [o2 . o2] — the new-residual norm accumulated
    while the o2 chunk is still in VMEM.
    """
    _require_1d("fused_axpy2_dots", x1, y1, x2, y2)
    (n,) = x1.shape
    chunk_eff, grid = _chunking(n, chunk)
    spec = pl.BlockSpec((chunk_eff,), lambda i: (i,))
    av = jnp.stack([jnp.asarray(a1, x1.dtype), jnp.asarray(a2, x1.dtype)])

    def kernel(a_ref, x1_ref, y1_ref, x2_ref, y2_ref, o1_ref, o2_ref, d_ref):
        i = pl.program_id(0)
        o1_ref[...] = a_ref[0] * x1_ref[...] + y1_ref[...]
        v2 = a_ref[1] * x2_ref[...] + y2_ref[...]
        o2_ref[...] = v2

        @pl.when(i == 0)
        def _init():
            d_ref[0] = jnp.zeros((), d_ref.dtype)

        valid = _valid_mask(i, chunk_eff, n)
        d_ref[0] += jnp.sum(jnp.where(valid, v2 * v2, jnp.zeros((), v2.dtype)))

    return pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * 4,
        out_specs=[spec, spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((n,), x1.dtype),
            jax.ShapeDtypeStruct((n,), x1.dtype),
            jax.ShapeDtypeStruct((1,), x1.dtype),
        ],
        interpret=interpret,
    )(av, x1, y1, x2, y2)


# ---------------------------------------------------------------------------
# Multi-RHS block kernels: (n, r) column blocks, one HBM pass each
# ---------------------------------------------------------------------------
#
# The block-CG hot path works on (n, r) column blocks instead of (n,)
# vectors. Same streaming discipline as above — every block is read once
# per call — but the reduction outputs are small (r, r) Gram matrices and
# the updates contract with (r, r) coefficient blocks:
#
# * ``block_gram``    — local Xᵀ·Y Gram blocks for a list of pairs, one
#   pass over the distinct operands. The (r, r) accumulators live in a
#   VMEM output block revisited at every grid step (index_map pins (0, 0)).
# * ``block_update``  — Y·diag(mask) + X @ M: the P-update of block-CG,
#   with the deflation column mask folded into the same pass.
# * ``block_update2`` — two independent block updates (the X/R pair) in
#   one pass over all four blocks.


def _require_block(op: str, *arrays):
    for a in arrays:
        if a.ndim != 2:
            raise ValueError(
                f"{op} expects 2-D (n, r) column blocks, got shape {a.shape}"
            )


def _dedup_pairs_ordered(pairs):
    """Like :func:`_dedup_pairs` but ORDER-SENSITIVE: XᵀY is the transpose
    of YᵀX, not the same product, so Gram pairs must not be symmetrized."""
    uniq: list = []
    ids: dict[int, int] = {}

    def idx(a):
        if id(a) not in ids:
            ids[id(a)] = len(uniq)
            uniq.append(a)
        return ids[id(a)]

    out_map = []
    prod_ids: dict[tuple[int, int], int] = {}
    prods = []
    for x, y in pairs:
        key = (idx(x), idx(y))
        if key not in prod_ids:
            prod_ids[key] = len(prods)
            prods.append(key)
        out_map.append(prod_ids[key])
    return uniq, tuple(prods), tuple(out_map)


def block_gram(pairs, *, chunk: int = 1024, interpret: bool = False):
    """Local Gram blocks ``[Xᵀ @ Y for (X, Y) in pairs]`` — ONE HBM pass.

    Returns a list of (r, r) LOCAL Grams (callers psum once). Operands
    shared between pairs are read once; identical ordered pairs are
    multiplied once. The ragged tail is masked on every operand so no
    out-of-range row can contribute.
    """
    uniq, prods, out_map = _dedup_pairs_ordered(pairs)
    _require_block("block_gram", *uniq)
    n, r = uniq[0].shape
    dt = uniq[0].dtype
    chunk_eff, grid = _chunking(n, chunk)
    spec = pl.BlockSpec((chunk_eff, r), lambda i: (i, 0))
    acc = pl.BlockSpec((r, r), lambda i: (0, 0))

    def kernel(*refs):
        ins, outs = refs[: len(uniq)], refs[len(uniq):]
        i = pl.program_id(0)
        for out_ref in outs:
            @pl.when(i == 0)
            def _init(out_ref=out_ref):
                out_ref[...] = jnp.zeros_like(out_ref)

        valid = _valid_rows(i, chunk_eff, n, r)
        zero = jnp.zeros((), dt)
        vals = [jnp.where(valid, t[...], zero) for t in ins]
        for j, (a, b) in enumerate(prods):
            outs[j][...] += jnp.dot(
                vals[a].T, vals[b], preferred_element_type=dt, precision=_F32
            )

    grams = pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[spec] * len(uniq),
        out_specs=[acc] * len(prods),
        out_shape=[jax.ShapeDtypeStruct((r, r), dt)] * len(prods),
        interpret=interpret,
    )(*uniq)
    return [grams[m] for m in out_map]


def block_update(m, x, y, mask=None, *, chunk: int = 1024,
                 interpret: bool = False):
    """``y * mask + x @ m`` in one pass; ``mask`` is an optional (r,)
    column scale (the block-CG deflation mask), broadcast over rows."""
    _require_block("block_update", x, y)
    n, r = x.shape
    chunk_eff, grid = _chunking(n, chunk)
    spec = pl.BlockSpec((chunk_eff, r), lambda i: (i, 0))
    mm = jnp.asarray(m, x.dtype).reshape(r, r)
    kv = (jnp.ones((1, r), x.dtype) if mask is None
          else jnp.asarray(mask, x.dtype).reshape(1, r))

    def kernel(m_ref, k_ref, x_ref, y_ref, o_ref):
        o_ref[...] = y_ref[...] * k_ref[...] + jnp.dot(
            x_ref[...], m_ref[...], preferred_element_type=o_ref.dtype,
            precision=_F32
        )

    return pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((r, r), lambda i: (0, 0)),
            pl.BlockSpec((1, r), lambda i: (0, 0)),
            spec, spec,
        ],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, r), x.dtype),
        interpret=interpret,
    )(mm, kv, x, y)


def block_update2(a1, x1, y1, a2, x2, y2, *, chunk: int = 1024,
                  interpret: bool = False):
    """``(y1 + x1 @ a1, y2 + x2 @ a2)`` in one pass over all four blocks —
    the block-CG X/R update (a2 = -alpha folds the sign into the
    coefficient block)."""
    _require_block("block_update2", x1, y1, x2, y2)
    n, r = x1.shape
    chunk_eff, grid = _chunking(n, chunk)
    spec = pl.BlockSpec((chunk_eff, r), lambda i: (i, 0))
    av = jnp.stack([
        jnp.asarray(a1, x1.dtype).reshape(r, r),
        jnp.asarray(a2, x1.dtype).reshape(r, r),
    ])

    def kernel(a_ref, x1_ref, y1_ref, x2_ref, y2_ref, o1_ref, o2_ref):
        o1_ref[...] = y1_ref[...] + jnp.dot(
            x1_ref[...], a_ref[0], preferred_element_type=o1_ref.dtype,
            precision=_F32
        )
        o2_ref[...] = y2_ref[...] + jnp.dot(
            x2_ref[...], a_ref[1], preferred_element_type=o2_ref.dtype,
            precision=_F32
        )

    return pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((2, r, r), lambda i: (0, 0, 0))] + [spec] * 4,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n, r), x1.dtype)] * 2,
        interpret=interpret,
    )(av, x1, y1, x2, y2)


# ---------------------------------------------------------------------------
# s-step CG kernels: the whole block's vector work in three HBM passes
# ---------------------------------------------------------------------------
#
# s-step CG does s iterations' worth of vector algebra per block: one fused
# Gram reduction over the (n, s) basis blocks, one A-conjugation +
# column-normalization update forming the search block, and one x/r update
# contracting with the (s,) step coefficients. Each op below is ONE pass:
#
# * ``sstep_gram``   — [PᵀW | WpᵀP | Pᵀr | rᵀr] flattened to
#   (2s² + s + 1,): every scalar the block solve needs from one read of
#   {P, W, Wp, r}. The caller psums the flat vector once; the basis
#   column A-norms that feed the stability scaling are ``diag(PᵀW)``, so
#   no extra payload rides the collective.
# * ``sstep_basis``  — (Pb·diag(d) − Qp @ B, Wb·diag(d) − Wp @ B): the
#   normalized A-conjugated search/image blocks in one pass over all four
#   (n, s) operands.
# * ``sstep_update`` — (x + Q @ a, r − WQ @ a) with an (s,) coefficient
#   vector, one pass over both blocks and both vectors.


def sstep_gram(pb, wb, wp, r, *, chunk: int = 1024, interpret: bool = False):
    """Local s-step reduction ``[PᵀW | WpᵀP | Pᵀr | rᵀr]`` — ONE HBM pass
    over the (n, s) blocks P, W, Wp and the (n,) residual.

    Returns a flat (2s² + s + 1,) vector of LOCAL partial sums (callers
    psum once). The (s, s) accumulators live in VMEM output blocks pinned
    at (0, 0); the s + 1 scalars accumulate in SMEM.
    """
    _require_block("sstep_gram", pb, wb, wp)
    _require_1d("sstep_gram", r)
    n, s = pb.shape
    dt = pb.dtype
    chunk_eff, grid = _chunking(n, chunk)
    spec = pl.BlockSpec((chunk_eff, s), lambda i: (i, 0))
    vspec = pl.BlockSpec((chunk_eff,), lambda i: (i,))
    acc = pl.BlockSpec((s, s), lambda i: (0, 0))

    def kernel(p_ref, w_ref, wp_ref, r_ref, gpp_ref, c_ref, v_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            gpp_ref[...] = jnp.zeros_like(gpp_ref)
            c_ref[...] = jnp.zeros_like(c_ref)
            for j in range(s + 1):
                v_ref[j] = jnp.zeros((), v_ref.dtype)

        valid = _valid_rows(i, chunk_eff, n, s)
        zero = jnp.zeros((), dt)
        p = jnp.where(valid, p_ref[...], zero)
        w = jnp.where(valid, w_ref[...], zero)
        wpv = jnp.where(valid, wp_ref[...], zero)
        rv = jnp.where(_valid_mask(i, chunk_eff, n), r_ref[...], zero)
        gpp_ref[...] += jnp.dot(
            p.T, w, preferred_element_type=dt, precision=_F32
        )
        c_ref[...] += jnp.dot(
            wpv.T, p, preferred_element_type=dt, precision=_F32
        )
        g = jnp.sum(p * rv[:, None], axis=0)
        for j in range(s):
            v_ref[j] += g[j]
        v_ref[s] += jnp.sum(rv * rv)

    gpp, c, v = pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[spec, spec, spec, vspec],
        out_specs=[acc, acc, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((s, s), dt),
            jax.ShapeDtypeStruct((s, s), dt),
            jax.ShapeDtypeStruct((s + 1,), dt),
        ],
        interpret=interpret,
    )(pb, wb, wp, r)
    return jnp.concatenate([gpp.reshape(-1), c.reshape(-1), v])


def sstep_basis(b, dinv, qp, pb, wp, wb, *, chunk: int = 1024,
                interpret: bool = False):
    """``(Pb·diag(dinv) − Qp @ b, Wb·diag(dinv) − Wp @ b)`` in ONE pass
    over all four (n, s) blocks — the s-step A-conjugation with the basis
    column normalization folded into the same sweep."""
    _require_block("sstep_basis", qp, pb, wp, wb)
    n, s = pb.shape
    chunk_eff, grid = _chunking(n, chunk)
    spec = pl.BlockSpec((chunk_eff, s), lambda i: (i, 0))
    bm = jnp.asarray(b, pb.dtype).reshape(s, s)
    kv = jnp.asarray(dinv, pb.dtype).reshape(1, s)

    def kernel(b_ref, k_ref, qp_ref, pb_ref, wp_ref, wb_ref, o1_ref, o2_ref):
        o1_ref[...] = pb_ref[...] * k_ref[...] - jnp.dot(
            qp_ref[...], b_ref[...], preferred_element_type=o1_ref.dtype,
            precision=_F32
        )
        o2_ref[...] = wb_ref[...] * k_ref[...] - jnp.dot(
            wp_ref[...], b_ref[...], preferred_element_type=o2_ref.dtype,
            precision=_F32
        )

    return pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((s, s), lambda i: (0, 0)),
            pl.BlockSpec((1, s), lambda i: (0, 0)),
            spec, spec, spec, spec,
        ],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n, s), pb.dtype)] * 2,
        interpret=interpret,
    )(bm, kv, qp, pb, wp, wb)


def sstep_update(a, q, wq, x, r, *, chunk: int = 1024,
                 interpret: bool = False):
    """``(x + Q @ a, r − WQ @ a)`` with an (s,) coefficient vector — the
    s-step solution/residual update, ONE pass over both (n, s) blocks and
    both (n,) vectors. The vectors ride through as (n, 1) column blocks so
    the contraction stays a single fused dot per output."""
    _require_block("sstep_update", q, wq)
    _require_1d("sstep_update", x, r)
    n, s = q.shape
    chunk_eff, grid = _chunking(n, chunk)
    spec = pl.BlockSpec((chunk_eff, s), lambda i: (i, 0))
    cspec = pl.BlockSpec((chunk_eff, 1), lambda i: (i, 0))
    av = jnp.asarray(a, q.dtype).reshape(s, 1)

    def kernel(a_ref, q_ref, wq_ref, x_ref, r_ref, ox_ref, or_ref):
        ox_ref[...] = x_ref[...] + jnp.dot(
            q_ref[...], a_ref[...], preferred_element_type=ox_ref.dtype,
            precision=_F32
        )
        or_ref[...] = r_ref[...] - jnp.dot(
            wq_ref[...], a_ref[...], preferred_element_type=or_ref.dtype,
            precision=_F32
        )

    ox, orr = pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((s, 1), lambda i: (0, 0)),
            spec, spec, cspec, cspec,
        ],
        out_specs=[cspec, cspec],
        out_shape=[jax.ShapeDtypeStruct((n, 1), q.dtype)] * 2,
        interpret=interpret,
    )(av, q, wq, x.reshape(n, 1), r.reshape(n, 1))
    return ox.reshape(n), orr.reshape(n)


# ---------------------------------------------------------------------------
# Legacy fixed-arity wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def fused_dots3(
    p: jax.Array, w: jax.Array, r: jax.Array, *, chunk: int = 65536,
    interpret: bool = False,
) -> jax.Array:
    """(n,) vectors -> (3,) [p·w, r·r, p·r]; any n (masked internally)."""
    return fused_dots_n(
        [(p, w), (r, r), (p, r)], chunk=chunk, interpret=interpret
    )
