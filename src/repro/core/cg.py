"""Distributed Conjugate Gradient solvers (the paper's C2).

Four variants — three mirroring BootCMatchGX, one beyond-paper:

* ``hs``    — the classical Hestenes–Stiefel PCG [23]. Two all-reduces per
  iteration in our implementation (the (p, Ap) dot, and a *fused* reduce of
  (r, z) + ||r||^2 — the library-style fusion the paper credits for part of
  its efficiency).
* ``fcg``   — the communication-reduced (flexible) CG: the single-
  synchronization Chronopoulos–Gear two-term recurrence, covering the
  Notay–Napov communication-reduction idea [24]: **one** fused all-reduce per
  iteration ((r, u), (w, u), ||r||^2 packed into a single psum). Tolerates a
  variable (flexible) preconditioner.
* ``sstep`` — s-step CG after Chronopoulos–Gear [25]: a block of ``s``
  iterations advances with **one** fused all-reduce (the whole Gram matrix
  P^T A P, the cross-block coupling W_prevᵀP, the moment vector Pᵀr, and
  ||r||² packed together). Monomial basis in (M A); A-conjugation against the
  previous block is reconstructed locally from the reduced Gram blocks, so no
  second reduction is needed. With the identity preconditioner and a matrix
  partitioned with ``halo_depth >= s``, the basis comes from the
  matrix-powers SpMV (``core/spmv.matrix_powers``): ONE widened halo
  exchange per block instead of s round-trips — the communication-avoiding
  formulation. The basis columns are rescaled by their A-norms
  (``diag(PᵀAP)``, already in the reduction) before the block solves, so
  the Gram conditioning stays near the conjugation's intrinsic one instead
  of growing like κ^s with the raw monomial columns; a non-finite block
  solve freezes x/r and exits the loop (loud non-convergence, not NaNs).
* ``pipecg`` — pipelined CG after Ghysels & Vanroose: like ``fcg`` it needs
  only **one** fused all-reduce per iteration, but the reduction is *issued
  before* the iteration's SpMV + preconditioner application, whose results
  it does not depend on — so the all-reduce latency (the dominant strong-
  scaling cost at high shard counts) hides behind the matvec instead of
  stalling it. Costs two extra vector recurrences (+1 fused HBM sweep/iter
  with the identity preconditioner); see ``docs/solvers.md`` for when the
  trade wins.

All solvers run entirely inside one ``shard_map`` region: vectors are local
(R,) shards, the matrix is a local DistMat block, and every collective is
explicit. The number of all-reduces per iteration is therefore *visible in
the lowered HLO* — which is what the roofline collective term measures.
"""

from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.partition import DistMat
from repro.core.spmv import (
    dist_specs,
    local_block,
    matrix_powers,
    overlap_default,
    spmv_shard,
)
from repro.core.vectors import all_reduce, fused_blocks, fused_dots, pdot
from repro.energy import trace
from repro.kernels import dispatch as kd


class Preconditioner(NamedTuple):
    """A distributed preconditioner: per-shard apply + its sharded state.

    ``apply(data_local, r_own, axis) -> z_own`` runs inside shard_map.
    ``localize(data)`` converts the global-view pytree to the per-shard view
    inside shard_map (default: squeeze the leading shard axis; replicated
    leaves — e.g. the AMG coarsest-level dense inverse — override this).
    """

    data: Any  # pytree of device arrays, leading shard axis on each leaf
    specs: Any  # matching PartitionSpec pytree
    apply: Callable[[Any, jax.Array, str], jax.Array]
    localize: Callable[[Any], Any] = None  # type: ignore[assignment]
    # True for the identity preconditioner: lets the solver bodies skip the
    # apply AND reuse the fused-kernel residual norm for (r, z) — one fewer
    # full-vector sweep per iteration.
    is_identity: bool = False


def _default_localize(data):
    return jax.tree.map(
        lambda a: a[0] if hasattr(a, "ndim") and a.ndim > 0 else a, data
    )


def _safe_div(num, den):
    """num/den, but 0 when den == 0 — guards the pre-loop step of the
    fcg/pipecg bodies against a zero initial residual (r0 = 0 makes every
    Gram scalar 0; the update must then be a no-op, not NaN)."""
    safe = jnp.where(den != 0, den, 1.0)
    return jnp.where(den != 0, num / safe, 0.0)


def identity_precond() -> Preconditioner:
    return Preconditioner(
        data=(), specs=(), apply=lambda data, r, axis: r,
        localize=lambda d: d, is_identity=True,
    )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("x", "iters", "rr", "bb"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class SolveResult:
    x: jax.Array  # (S, R) padded sharded solution
    iters: jax.Array  # scalar int
    rr: jax.Array  # final ||r||^2
    bb: jax.Array  # ||b||^2 (for relative residual)

    @property
    def rel_residual(self):
        return jnp.sqrt(self.rr / jnp.maximum(self.bb, 1e-300))


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("x", "iters", "iters_cols", "rr", "bb"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class BlockSolveResult:
    """Result of a multi-RHS block solve (``make_block_solver``)."""

    x: jax.Array  # (S, R, r) padded sharded solution block
    iters: jax.Array  # scalar int — iterations until the LAST column converged
    iters_cols: jax.Array  # (r,) iteration at which each column first converged
    rr: jax.Array  # (r,) final per-column ||r_j||^2
    bb: jax.Array  # (r,) per-column ||b_j||^2

    @property
    def rel_residual(self):
        """(r,) per-column relative residuals."""
        return jnp.sqrt(self.rr / jnp.maximum(self.bb, 1e-300))


# ---------------------------------------------------------------------------
# Per-shard solver bodies (inside shard_map)
# ---------------------------------------------------------------------------


def _telemetry_emit(i, relres, axis):
    """Bake the per-iteration convergence callback into the loop body.

    Called at trace time only when the solver was built with
    ``telemetry=True`` (repro.obs.convergence): the compiled program then
    reports ``(i, relres)`` to the host once per *executed* iteration.
    """
    from repro.obs import convergence

    convergence.instrument(i, relres, axis)


def _hs_body(A, pre: Preconditioner, pdata, b, x0, *, tol, maxiter, axis, ops,
             telemetry=False):
    """Hestenes–Stiefel PCG; 2 all-reduces/iter (one fused).

    Hot-loop vector work runs through the kernel dispatch ``ops``: with the
    identity preconditioner each iteration is 3 full-vector HBM sweeps
    outside the SpMV (p·w dot; fused x/r update + ||r||²; p update) instead
    of the ~6 of the op-by-op formulation. A non-trivial preconditioner adds
    one sweep for the fused (r·z, r·r) reduction.

    Components are region-marked (energy/trace.py): the SpMV, the fused
    reductions/updates, and the preconditioner apply each attribute their
    executed counts to their own energy region.
    """
    with trace.region("spmv"):
        r = b - A(x0)
    with trace.region("precond"):
        z = pre.apply(pdata, r, axis)
    with trace.region("reductions"):
        d0 = fused_dots([(r, z), (r, r), (b, b)], axis)
    rz, rr, bb = d0[0], d0[1], d0[2]
    tol2 = tol * tol * bb

    def cond(c):
        i, x, r, z, p, rz, rr = c
        return (i < maxiter) & (rr > tol2)

    def body(c):
        i, x, r, z, p, rz, rr = c
        with kd.ledger_section("iteration"):
            with trace.region("spmv"):
                w = A(p)
            with trace.region("reductions"):
                pw = all_reduce(ops.fused_dots_n([(p, w)])[0], axis)  # all-reduce 1
                trace.record_collective(1, w.dtype.itemsize)
                alpha = rz / pw
                # x += alpha p ; r -= alpha w ; local r'.r' — ONE pass
                x, r, rr_loc = ops.fused_axpy2_dots(alpha, p, x, -alpha, w, r)
            if pre.is_identity:
                z = r
                with trace.region("reductions"):
                    rr = all_reduce(rr_loc[0], axis)  # all-reduce 2
                    trace.record_collective(1, w.dtype.itemsize)
                rz_new = rr
            else:
                with trace.region("precond"):
                    z = pre.apply(pdata, r, axis)
                with trace.region("reductions"):
                    rz_loc = ops.fused_dots_n([(r, z)])[0]
                    d = all_reduce(jnp.stack([rz_loc, rr_loc[0]]), axis)  # AR 2 (fused)
                    trace.record_collective(2, w.dtype.itemsize)
                rz_new, rr = d[0], d[1]
            with trace.region("reductions"):
                beta = rz_new / rz
                p = ops.axpy(beta, p, z)
        if telemetry:
            _telemetry_emit(i + 1, jnp.sqrt(rr / jnp.maximum(bb, 1e-300)), axis)
        return (i + 1, x, r, z, p, rz_new, rr)

    i0 = jnp.asarray(0, jnp.int32)
    c = lax.while_loop(cond, body, (i0, x0, r, z, z, rz, rr))
    return c[1], c[0], c[6], bb


def _fcg_body(A, pre: Preconditioner, pdata, b, x0, *, tol, maxiter, axis, ops,
              telemetry=False):
    """Single-synchronization (communication-reduced flexible) CG.

    Chronopoulos–Gear two-term recurrence: ONE fused all-reduce per
    iteration. Hot-loop vector work runs through the kernel dispatch
    ``ops`` in 3 full-vector HBM sweeps outside the SpMV: the fused triple
    dot (reads {r, u, w} once — u aliases r under the identity
    preconditioner), the fused p/s update, and the fused x/r update.

    Components are region-marked (energy/trace.py) exactly as in the HS
    body: spmv / reductions / precond.
    """
    with trace.region("spmv"):
        r = b - A(x0)
    with trace.region("precond"):
        u = pre.apply(pdata, r, axis)
    with trace.region("spmv"):
        w = A(u)
    with trace.region("reductions"):
        d0 = fused_dots([(r, u), (w, u), (r, r), (b, b)], axis)
    gamma, delta, rr, bb = d0[0], d0[1], d0[2], d0[3]
    tol2 = tol * tol * bb

    alpha = _safe_div(gamma, delta)  # r0 == 0 -> no-op first step, not NaN
    p, s = u, w
    x = x0 + alpha * p
    r = r - alpha * s

    def cond(c):
        i, x, r, p, s, gamma, alpha, rr = c
        return (i < maxiter) & (rr > tol2)

    def body(c):
        i, x, r, p, s, gamma, alpha, rr = c
        with kd.ledger_section("iteration"):
            if pre.is_identity:
                u = r
            else:
                with trace.region("precond"):
                    u = pre.apply(pdata, r, axis)
            with trace.region("spmv"):
                w = A(u)
            with trace.region("reductions"):
                d = all_reduce(  # the ONE all-reduce
                    ops.fused_dots_n([(r, u), (w, u), (r, r)]), axis
                )
                trace.record_collective(3, w.dtype.itemsize)
                gamma_new, delta, rr = d[0], d[1], d[2]
                beta = gamma_new / gamma
                alpha_new = gamma_new / (delta - beta * gamma_new / alpha)
                p, s = ops.fused_axpy2(beta, p, u, beta, s, w)  # p=u+βp ; s=w+βs
                x, r = ops.fused_axpy2(alpha_new, p, x, -alpha_new, s, r)
        if telemetry:
            # rr here is ||r||² *before* this body's update (the fused
            # reduction reads the incoming residual) — the reported curve
            # lags the true residual by one iteration
            _telemetry_emit(i + 1, jnp.sqrt(rr / jnp.maximum(bb, 1e-300)), axis)
        return (i + 1, x, r, p, s, gamma_new, alpha_new, rr)

    i0 = jnp.asarray(1, jnp.int32)
    c = lax.while_loop(cond, body, (i0, x, r, p, s, gamma, alpha, rr))
    return c[1], c[0], c[7], bb


def _pipecg_body(
    A, pre: Preconditioner, pdata, b, x0, *, tol, maxiter, axis, ops,
    overlap=True, telemetry=False,
):
    """Ghysels–Vanroose pipelined PCG: ONE all-reduce/iter, hidden.

    The fused reduction (gamma = r·u, delta = w·u, ||r||²) is issued at the
    top of the body; the SpMV ``n = A (M w)`` that follows does not depend
    on its result, so XLA schedules the all-reduce concurrently with the
    matvec — with ``overlap=True`` both are attributed to the ``"overlap"``
    energy region (modeled hidden; energy/trace.py). The price is the extra
    z (and q, under a real preconditioner) recurrences: 4 full-vector HBM
    sweeps per iteration outside the SpMV with the identity preconditioner
    (3 fused axpy2 passes + the fused dot pass) vs 3 for hs/fcg.

    The convergence check uses the ||r||² from the fused reduction, which
    lags the updated residual by one iteration — the standard pipelined-CG
    trade of one extra iteration for the hidden latency.
    """
    # -- init: r0, u0 = M r0, w0 = A u0, first reduction + first update -----
    with trace.region("spmv"):
        r = b - A(x0)
    if pre.is_identity:
        u = r
    else:
        with trace.region("precond"):
            u = pre.apply(pdata, r, axis)
    with trace.region("spmv"):
        w = A(u)
    with trace.region("reductions"):
        d0 = fused_dots([(r, u), (w, u), (r, r), (b, b)], axis)
    gamma, delta, rr, bb = d0[0], d0[1], d0[2], d0[3]
    tol2 = tol * tol * bb

    if pre.is_identity:
        m = w
    else:
        with trace.region("precond"):
            m = pre.apply(pdata, w, axis)
    with trace.region("spmv"):
        n = A(m)
    alpha = _safe_div(gamma, delta)  # r0 == 0 -> no-op first step, not NaN
    z, q, s_, p = n, m, w, u
    x = x0 + alpha * p
    r = r - alpha * s_
    u = r if pre.is_identity else u - alpha * q
    w = w - alpha * z

    def _reduce(r, u, w):
        """Issue the ONE fused all-reduce (the SpMV that follows does not
        depend on its result — that independence is the pipeline)."""
        pairs = (
            [(w, r), (r, r)] if pre.is_identity else [(r, u), (w, u), (r, r)]
        )
        d = all_reduce(ops.fused_dots_n(pairs), axis)
        trace.record_collective(len(pairs), w.dtype.itemsize)
        return d

    def _precond_w(w):
        if pre.is_identity:
            return w
        with trace.region("precond"):
            return pre.apply(pdata, w, axis)

    def body(c):
        i, x, r, u, w, p, s_, q, z, gamma, alpha, rr = c
        with kd.ledger_section("iteration"):
            if overlap:
                # reduction + concurrent SpMV: one co-scheduled phase
                with trace.region(trace.OVERLAP):
                    d = _reduce(r, u, w)
                    m = _precond_w(w)
                    n = A(m)
            else:
                # serialized A/B reference: the reduction blocks, then the
                # SpMV runs — attributed like the hs/fcg bodies
                with trace.region("reductions"):
                    d = _reduce(r, u, w)
                m = _precond_w(w)
                with trace.region("spmv"):
                    n = A(m)
            if pre.is_identity:
                delta, gamma_new, rr = d[0], d[1], d[1]
            else:
                gamma_new, delta, rr = d[0], d[1], d[2]
            beta = gamma_new / gamma
            alpha_new = gamma_new / (delta - beta * gamma_new / alpha)
            with trace.region("reductions"):
                if pre.is_identity:
                    # 3 fused passes: (z, s), (p, w), (x, r); u == r, q == s
                    z, s_ = ops.fused_axpy2(beta, z, n, beta, s_, w)
                    p, w = ops.fused_axpy2(beta, p, r, -alpha_new, z, w)
                    x, r = ops.fused_axpy2(alpha_new, p, x, -alpha_new, s_, r)
                    u, q = r, s_
                else:
                    z, q = ops.fused_axpy2(beta, z, n, beta, q, m)
                    s_, p = ops.fused_axpy2(beta, s_, w, beta, p, u)
                    x, r = ops.fused_axpy2(alpha_new, p, x, -alpha_new, s_, r)
                    u, w = ops.fused_axpy2(-alpha_new, q, u, -alpha_new, z, w)
        if telemetry:
            # pipelined trade-off: rr lags the updated residual by one iter
            _telemetry_emit(i + 1, jnp.sqrt(rr / jnp.maximum(bb, 1e-300)), axis)
        return (i + 1, x, r, u, w, p, s_, q, z, gamma_new, alpha_new, rr)

    def cond(c):
        i, x, r, u, w, p, s_, q, z, gamma, alpha, rr = c
        return (i < maxiter) & (rr > tol2)

    i0 = jnp.asarray(1, jnp.int32)
    c = lax.while_loop(
        cond, body, (i0, x, r, u, w, p, s_, q, z, gamma, alpha, rr)
    )
    return c[1], c[0], c[11], bb


def _gram_solve(G, rhs):
    """``G^{-1} rhs`` for a small (k, k) Gram matrix ``G``.

    Gaussian elimination with partial pivoting and back substitution,
    written in plain array ops (no matmul, so no reduced-precision MXU
    pass either): it compiles for every dtype the device has, where
    ``jnp.linalg.solve`` needs an LU routine that the TPU lacks in f64. A
    singular ``G`` yields non-finite entries, as LU does, for the callers'
    breakdown guards.
    """
    k = G.shape[0]
    m = jnp.concatenate([G, rhs.reshape(k, -1)], axis=1)
    rows = jnp.arange(k)

    def eliminate(j, m):
        col = jnp.abs(m[:, j])
        p = jnp.argmax(jnp.where(rows >= j, col, -1.0))
        m = m.at[j].set(m[p]).at[p].set(m[j])
        f = jnp.where(rows > j, m[:, j] / m[j, j], 0.0)
        return m - f[:, None] * m[j][None, :]

    def substitute(i, x):
        j = k - 1 - i
        done = jnp.sum(m[j, :k, None] * x, axis=0)  # rows > j only
        return x.at[j].set((m[j, k:] - done) / m[j, j])

    m = lax.fori_loop(0, k, eliminate, m)
    x = lax.fori_loop(0, k, substitute, jnp.zeros_like(m[:, k:]))
    return x.reshape(rhs.shape)


def _sstep_body(
    A, pre: Preconditioner, pdata, b, x0, *, tol, maxiter, s, axis, ops,
    mat=None, telemetry=False,
):
    """s-step CG (Chronopoulos–Gear): one fused all-reduce per s iterations.

    Monomial basis P = [u, (MA)u, ..., (MA)^{s-1}u] with u = M r; the block
    is A-conjugated against the previous block using only locally
    reconstructable Gram algebra (see module docstring).

    Basis construction routes through :func:`~repro.core.spmv.matrix_powers`
    when it can — identity preconditioner and a ``mat`` partitioned with
    ghost zones at least ``s`` deep — replacing the s sequential halo
    round-trips of the naive loop with ONE widened exchange per block (the
    communication-avoiding formulation). Otherwise the sequential scan is
    the fallback (real preconditioner, shallow halo, or all-gather layout).

    Vector work runs through the kernel dispatch ``ops`` in 3 full-vector
    HBM sweeps per block outside the SpMVs: the fused Gram reduction
    (``sstep_gram``), the A-conjugation + column-normalization update
    (``sstep_basis``), and the x/r update (``sstep_update``).

    Stability: the monomial columns are rescaled by their A-norms (the
    reduced ``diag(PᵀW)`` — no extra collective payload) before the block
    solves, and a non-finite step freezes x/r and exits the loop.
    """
    dt = b.dtype
    R = b.shape[0]
    with trace.region("spmv"):
        r = b - A(x0)
    with trace.region("reductions"):
        bb = pdot(b, b, axis)
    tol2 = tol * tol * bb
    eye = jnp.eye(s, dtype=dt)

    # the matrix-powers path needs ghost zones covering all s applications
    # (a lone shard has no halo at all — any depth works there)
    use_mp = (
        mat is not None
        and pre.is_identity
        and mat.plan.mode != "allgather"
        and (not mat.plan.shifts or mat.halo_depth >= s)
    )

    def build_basis(r):
        if use_mp:
            # ONE widened exchange for the whole block: [Ar, ..., A^s r]
            Ws = matrix_powers(mat, r, s, axis)
            Ps = jnp.concatenate([r[None], Ws[:-1]], axis=0)
            return Ps.T, Ws.T  # (s, R) -> (R, s)

        def one(carry, _):
            u = carry
            with trace.region("precond"):
                p = pre.apply(pdata, u, axis)
            with trace.region("spmv"):
                w = A(p)
            return w, (p, w)

        # the scan body traces ONCE but executes s times per block — scale
        # its recorded counts accordingly (see energy/trace.py)
        with trace.repeated(s):
            _, (Ps, Ws) = lax.scan(one, r, None, length=s)
        # (s, R) -> (R, s)
        return Ps.T, Ws.T

    def body(c):
        # The while body traces ONCE per s-iteration BLOCK, but the ledger
        # replays iteration-section counts once per ITERATION — record the
        # block's counts at their per-iteration average so sstep ledgers
        # are comparable with hs/fcg (one widened exchange per block shows
        # up as 1/s collectives per iteration, exactly the amortization).
        with kd.ledger_section("iteration"), trace.repeated(1.0 / s):
            return _sstep_block(c)

    def _sstep_block(c):
        i, ok, x, r, Qp, Wp, Gqq, rr = c
        Pb, Wb = build_basis(r)
        # ONE fused all-reduce: [P^T W (s*s) | W_prev^T P (s*s) | P^T r (s) | rr]
        with trace.region("reductions"):
            flat = fused_blocks([ops.sstep_gram(Pb, Wb, Wp, r)], axis)
        Gpp = flat[: s * s].reshape(s, s)
        C = flat[s * s : 2 * s * s].reshape(s, s)
        g = flat[2 * s * s : 2 * s * s + s]
        rr = flat[-1]
        # Rescale the basis columns by their A-norms (van der Sluis: the
        # diagonal scaling that near-minimizes the Gram condition number).
        # Raw monomial columns grow like rho(A)^j, so without this the Gram
        # conditioning explodes like kappa^s for large s.
        d = jnp.diagonal(Gpp)
        dinv = jnp.where(d > 0, lax.rsqrt(jnp.where(d > 0, d, 1.0)), 1.0)
        Gpp = Gpp * (dinv[:, None] * dinv[None, :])
        C = C * dinv[None, :]
        g = g * dinv
        # A-conjugate against previous block: B = Gqq^{-1} C (Gqq from prev).
        B = _gram_solve(Gqq + 1e-300 * eye, C)
        with trace.region("reductions"):
            # Q = Pb D - Qp B ; WQ = Wb D - Wp B — ONE fused pass
            Q, WQ = ops.sstep_basis(B, dinv, Qp, Pb, Wp, Wb)
        Gq = Gpp - B.T @ C - C.T @ B + B.T @ Gqq @ B
        # Q^T r == g because r ⟂ span(previous block) in exact arithmetic.
        a = _gram_solve(Gq + 1e-300 * eye, g)
        # breakdown guard: a non-finite step means the basis lost numerical
        # independence despite the scaling (s too large for this spectrum).
        # Freeze x/r and stop — the caller sees a loud non-converged
        # residual instead of silent NaNs.
        fin = jnp.isfinite(a).all() & jnp.isfinite(B).all()
        a = jnp.where(fin, a, jnp.zeros_like(a))
        with trace.region("reductions"):
            # x += Q a ; r -= WQ a — ONE fused pass
            x, r = ops.sstep_update(a, Q, WQ, x, r)
        if telemetry:
            # one report per s-iteration block; rr is the block-entry
            # residual (the fused Gram reads the incoming r)
            _telemetry_emit(i + s, jnp.sqrt(rr / jnp.maximum(bb, 1e-300)), axis)
        return (i + s, ok & fin, x, r, Q, WQ, Gq, rr)

    def cond(c):
        i, ok, x, r, Qp, Wp, Gqq, rr = c
        return ok & (i < maxiter) & (rr > tol2)

    i0 = jnp.asarray(0, jnp.int32)
    ok0 = jnp.asarray(True)
    # mark the zero-init blocks as shard-varying for the while_loop carry
    ax_names = (axis,) if isinstance(axis, str) else tuple(axis)
    Q0 = lax.pcast(jnp.zeros((R, s), dt), ax_names, to="varying")
    c = lax.while_loop(cond, body, (i0, ok0, x0, r, Q0, Q0, eye, bb))
    return c[2], c[0], c[7], bb


def _block_hs_body(A, B, X0, *, tol, maxiter, axis, ops, telemetry=False):
    """Breakdown-guarded block Hestenes–Stiefel CG for (R, r) RHS blocks.

    The scalar recurrences become r×r Gram algebra: alpha/beta are small
    matrix solves against the P'AP and R'R Grams, and the matrix is read
    ONCE per iteration for all r right-hand sides (the SpMM interior).
    Still 2 all-reduces/iter — each now carries r² scalars instead of 1.

    Guard policy (see docs/solvers.md):
      * deflation — a column whose residual has met its per-column target
        is masked out of both Gram solves (its alpha/beta columns are
        exactly zero, freezing x_j and r_j) and its search direction is
        zeroed, so a converged system cannot re-pollute the block;
      * ridge — the masked Grams get a trace-scaled ``eps`` ridge before
        the solve, so (near-)linearly-dependent RHS columns degrade the
        step slightly instead of producing NaNs (rank-deficient P'W).
    """
    dt = B.dtype
    nrhs = B.shape[1]
    eye = jnp.eye(nrhs, dtype=dt)

    with trace.region("spmv"):
        R_ = B - A(X0)
    with trace.region("reductions"):
        rr0_loc, bb_loc = ops.block_gram([(R_, R_), (B, B)])
        d0 = fused_blocks([rr0_loc, jnp.diagonal(bb_loc)], axis)
    RR = d0[: nrhs * nrhs].reshape(nrhs, nrhs)
    bb = d0[nrhs * nrhs :]
    tol2 = tol * tol * bb  # per-column targets

    def _msolve(G, RHS, md):
        # mask converged rows/cols out, keep the system well-posed with a
        # unit diagonal there, and ridge against RHS-column collinearity
        m2 = md[:, None] * md[None, :]
        Gm = G * m2 + jnp.diag(1.0 - md)
        ridge = jnp.finfo(dt).eps * jnp.trace(Gm) / nrhs
        return _gram_solve(Gm + ridge * eye, RHS * m2)

    def cond(c):
        i, X, R_, Pb, RR, it_cols = c
        return (i < maxiter) & jnp.any(jnp.diagonal(RR) > tol2)

    def body(c):
        i, X, R_, Pb, RR, it_cols = c
        md = (jnp.diagonal(RR) > tol2).astype(dt)  # 1 = still active
        with kd.ledger_section("iteration"):
            with trace.region("spmv"):
                W = A(Pb)  # matrix read once for all r columns
            with trace.region("reductions"):
                pw_loc = ops.block_gram([(Pb, W)])[0]
                PW = fused_blocks([pw_loc], axis).reshape(nrhs, nrhs)  # AR 1
                alpha = _msolve(PW, RR, md)
                # X += P alpha ; R -= W alpha — ONE fused pass
                X, R_ = ops.block_update2(alpha, Pb, X, -alpha, W, R_)
                rr_loc = ops.block_gram([(R_, R_)])[0]
                RRn = fused_blocks([rr_loc], axis).reshape(nrhs, nrhs)  # AR 2
                beta = _msolve(RR, RRn, md)
                Pb = ops.block_update(beta, Pb, R_, mask=md)
        it_cols = jnp.where(
            jnp.diagonal(RRn) <= tol2, jnp.minimum(it_cols, i + 1), it_cols
        )
        if telemetry:
            # per-column relative residuals: the history rows are vectors
            _telemetry_emit(
                i + 1,
                jnp.sqrt(jnp.diagonal(RRn) / jnp.maximum(bb, 1e-300)),
                axis,
            )
        return (i + 1, X, R_, Pb, RRn, it_cols)

    i0 = jnp.asarray(0, jnp.int32)
    maxit = jnp.asarray(maxiter, jnp.int32)
    it0 = jnp.where(
        jnp.diagonal(RR) <= tol2, jnp.zeros_like(maxit), maxit
    ).astype(jnp.int32)
    c = lax.while_loop(cond, body, (i0, X0, R_, R_, RR, it0))
    return c[1], c[0], c[5], jnp.diagonal(c[4]), bb


_BODIES = {
    "hs": _hs_body,
    "fcg": _fcg_body,
    "pipecg": _pipecg_body,
    "sstep": _sstep_body,
}
VARIANTS = tuple(_BODIES)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def _refuse_on_tpu(variant: str, mat: DistMat, ops) -> None:
    """Refuse, on a TPU backend, the solver paths a v5e computed wrong.

    Block-HS with the jnp reference block ops (every f64 run, or
    ``kernels='jnp'``) returned NaN in f32 and did not converge in f64, and
    f32 ``sstep`` did not converge with either kernel set. f64 ``sstep``
    and f32 block-HS on the Pallas block kernels converged (PERF.md).
    """
    if jax.default_backend() != "tpu":
        return
    f64 = jnp.dtype(mat.data_ext.dtype).itemsize == 8
    if variant == "block" and (f64 or ops.backend == "jnp"):
        raise ValueError(
            "block-HS (nrhs > 1) does not run on a TPU with the jnp block "
            "ops (f64, or kernels='jnp'): on a v5e it returned NaN in f32 "
            "and did not converge in f64. Solve one right-hand side at a "
            "time (serve with --slots 1), or in f32 with the Pallas kernels."
        )
    if variant == "sstep" and not f64:
        raise ValueError(
            "sstep does not converge in f32 on a TPU (seen on a v5e with "
            "both kernel sets): solve in f64 or use variant 'hs'."
        )


def make_solver(
    mesh,
    mat: DistMat,
    *,
    variant: str = "hs",
    precond: Preconditioner | None = None,
    tol: float = 1e-8,
    maxiter: int = 100,
    s: int = 2,
    axis="shards",  # mesh axis name, or a (rows, cols) tuple for 2-D grids
    kernels: str | None = None,
    overlap: bool = True,
    telemetry: bool = False,
):
    """Build a jitted distributed solver: ``solve(b, x0) -> SolveResult``.

    Args:
        mesh: 1-D ``jax.sharding.Mesh`` with a ``shards`` axis (see
            ``launch/mesh.py``).
        mat: the distributed matrix (leading shard axis on every data leaf;
            build with ``partition_csr`` / ``partition_stencil`` +
            ``spmv.shard_matrix``).
        variant: ``"hs"`` | ``"fcg"`` | ``"pipecg"`` | ``"sstep"`` — see the
            module docstring and ``docs/solvers.md`` for the trade-offs.
        precond: a :class:`Preconditioner` (None = identity).
        tol: relative residual target; convergence is declared at
            ``||r||^2 <= tol^2 * ||b||^2``.
        maxiter: iteration cap (an s-step block counts as ``s`` iterations).
        s: block size for ``variant="sstep"`` (ignored otherwise).
        axis: shard_map mesh-axis name the collectives run over.
        kernels: hot-path backend for the solver bodies — one of
            ``kernels.dispatch.BACKENDS`` or None/'auto' (resolve from
            override/env/backend). All four variants route through it;
            the sstep body's blocked Gram algebra uses the fused
            ``sstep_gram`` / ``sstep_basis`` / ``sstep_update`` ops.
        overlap: communication-hiding schedule (default on): the SpMV uses
            the interior/boundary split with the halo exchange in flight,
            and ``pipecg`` issues its all-reduce before the concurrent
            SpMV. ``False`` restores the serialized order (for A/B energy
            comparisons — see ``benchmarks/overlap_scaling.py``).
        telemetry: bake a per-iteration convergence callback into the loop
            body (repro.obs.convergence) — the compiled program reports
            ``(iteration, relres)`` to the host while it runs. Off by
            default: the callback is part of the compiled program, so this
            flag is part of the solver-handle cache key.

    Returns:
        A jitted ``solve(b, x0) -> SolveResult`` where ``b``/``x0`` are
        (S, R) padded sharded arrays (``partition.pad_vector`` +
        ``spmv.shard_vector``) and the result carries the (S, R) solution,
        the executed iteration count, and ``||r||^2`` / ``||b||^2``.
    """
    pre = precond or identity_precond()
    body = _BODIES[variant]
    kw = dict(
        tol=tol, maxiter=maxiter, axis=axis, ops=kd.ops_for(kernels),
        telemetry=telemetry,
    )
    _refuse_on_tpu(variant, mat, kw["ops"])
    if variant == "sstep":
        kw["s"] = s
    if variant == "pipecg":
        kw["overlap"] = overlap

    mat_specs = dist_specs(mat, axis)

    localize = pre.localize or _default_localize

    def fn(m, pdata, b, x0):
        mb = local_block(m)
        pl = localize(pdata)
        A = lambda v: spmv_shard(mb, v, axis, overlap=overlap)
        # the sstep body takes the local matrix block itself: its basis can
        # route through the matrix-powers SpMV (one widened halo exchange)
        kwb = dict(kw, mat=mb) if variant == "sstep" else kw
        # scope the default so preconditioner-internal SpMVs (the AMG
        # V-cycle's smoothers) follow the solver's schedule too
        with overlap_default(overlap):
            x, iters, rr, bb = body(A, pre, pl, b[0], x0[0], **kwb)
        return x[None], iters, rr, bb

    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(mat_specs, pre.specs, P(axis, None), P(axis, None)),
        out_specs=(P(axis, None), P(), P(), P()),
        check_vma=False,  # loop carries are not annotated as shard-varying
    )

    @jax.jit
    def solve(b, x0):
        x, iters, rr, bb = mapped(mat, pre.data, b, x0)
        return SolveResult(x=x, iters=iters, rr=rr, bb=bb)

    return solve


def make_solver_fn(
    mesh,
    mat_like: DistMat,
    *,
    variant: str = "hs",
    precond: Preconditioner | None = None,
    tol: float = 1e-8,
    maxiter: int = 100,
    s: int = 2,
    axis="shards",  # mesh axis name, or a (rows, cols) tuple for 2-D grids
    kernels: str | None = None,
    overlap: bool = True,
):
    """Lowerable variant of :func:`make_solver`: returns a jitted
    ``solve(mat, b, x0)`` with the matrix as a *runtime argument* — accepts
    ShapeDtypeStruct trees, which is what the production-mesh dry-run lowers
    (no data, no allocation).

    ``mat_like`` only supplies shapes/plan for the sharding specs; all other
    arguments as in :func:`make_solver`.
    """
    pre = precond or identity_precond()
    body = _BODIES[variant]
    kw = dict(tol=tol, maxiter=maxiter, axis=axis, ops=kd.ops_for(kernels))
    _refuse_on_tpu(variant, mat_like, kw["ops"])
    if variant == "sstep":
        kw["s"] = s
    if variant == "pipecg":
        kw["overlap"] = overlap
    mat_specs = dist_specs(mat_like, axis)
    localize = pre.localize or _default_localize

    def fn(m, pdata, b, x0):
        mb = local_block(m)
        pl = localize(pdata)
        A = lambda v: spmv_shard(mb, v, axis, overlap=overlap)
        kwb = dict(kw, mat=mb) if variant == "sstep" else kw
        with overlap_default(overlap):
            x, iters, rr, bb = body(A, pre, pl, b[0], x0[0], **kwb)
        return x[None], iters, rr, bb

    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(mat_specs, pre.specs, P(axis, None), P(axis, None)),
        out_specs=(P(axis, None), P(), P(), P()),
        check_vma=False,  # loop carries are not annotated as shard-varying
    )

    @jax.jit
    def solve(mat_arg, b, x0):
        x, iters, rr, bb = mapped(mat_arg, pre.data, b, x0)
        return SolveResult(x=x, iters=iters, rr=rr, bb=bb)

    return solve


def abstract_stencil_dist(p, n_shards: int, dtype="float64") -> DistMat:
    """ShapeDtypeStruct DistMat (ELL interior) for a slab-partitioned
    stencil problem —
    production-scale dry-runs lower this without ever materializing data."""
    import numpy as np

    from repro.core.partition import ELLBlock, HaloPlan, plane_partition

    part = plane_partition(p.n, p.plane, n_shards)
    R = part.max_own
    H = p.plane
    k = p.k
    off_dz_pos = {"7pt": 1, "27pt": 9}[p.stencil]
    k_ext = max(off_dz_pos, 1)
    shifts, widths = ((-1, 1), (H, H)) if n_shards > 1 else ((), ())
    plan = HaloPlan("ring", shifts, widths, R, n_shards)
    S = n_shards
    # boundary rows live in the slab's first/last plane (see
    # partition_stencil): 2H for interior shards, H for the 2-shard case
    if S <= 1:
        B, n_bnd = 1, (0,) * S
    elif S == 2:
        B, n_bnd = H, (H,) * S
    else:
        B = H * min(2, R // H)
        n_bnd = (H,) + (B,) * (S - 2) + (H,)
    sds = jax.ShapeDtypeStruct
    return DistMat(
        interior=ELLBlock(
            data=sds((S, R, k), dtype), col=sds((S, R, k), "int32")
        ),
        data_ext=sds((S, B, k_ext), dtype),
        col_ext=sds((S, B, k_ext), "int32"),
        bnd_rows=sds((S, B), "int32"),
        send_sel=sds((S, max(sum(widths), 1)), "int32"),
        plan=plan,
        n_global=p.n,
        row_starts=part.row_starts,
        n_bnd=n_bnd,
    )


def solve_cg(mesh, mat: DistMat, b_np, *, x0_np=None, **kw) -> SolveResult:
    """Convenience host-level solve: numpy in, SolveResult out."""
    import numpy as np

    from repro.core.partition import pad_vector
    from repro.core.spmv import shard_vector

    bp = pad_vector(np.asarray(b_np), mat)
    xp = (
        pad_vector(np.asarray(x0_np), mat)
        if x0_np is not None
        else np.zeros_like(bp)
    )
    solver = make_solver(mesh, mat, **kw)
    return solver(shard_vector(mesh, bp), shard_vector(mesh, xp))


def make_block_solver(
    mesh,
    mat: DistMat,
    *,
    precond: Preconditioner | None = None,
    tol: float = 1e-8,
    maxiter: int = 100,
    axis="shards",  # mesh axis name, or a (rows, cols) tuple for 2-D grids
    kernels: str | None = None,
    overlap: bool = True,
    telemetry: bool = False,
):
    """Build a jitted multi-RHS block solver: ``solve(B, X0) -> BlockSolveResult``.

    ``B``/``X0`` are (S, R, r) padded sharded blocks (``partition.pad_block``
    + ``spmv.shard_vector``). Runs the breakdown-guarded block-HS body: the
    matrix is streamed from HBM once per iteration for all ``r`` right-hand
    sides, converged columns are deflated, and each column's convergence is
    declared against its own ``tol^2 * ||b_j||^2`` target.

    Only the identity preconditioner is supported (the block recurrences
    assume the unpreconditioned R'R Gram); pass ``precond=None``.
    ``solve.func`` is the jitted ``solve(mat, B, X0)`` with the matrix as a
    runtime argument, lowerable from ShapeDtypeStruct trees like
    :func:`make_solver_fn`.
    """
    if precond is not None and not precond.is_identity:
        raise ValueError(
            "block-CG supports the identity preconditioner only; "
            "use make_solver(variant=...) per column for preconditioned solves"
        )
    ops = kd.ops_for(kernels)
    _refuse_on_tpu("block", mat, ops)
    kw = dict(tol=tol, maxiter=maxiter, axis=axis, ops=ops,
              telemetry=telemetry)
    mat_specs = dist_specs(mat, axis)

    def fn(m, Bv, X0):
        mb = local_block(m)
        A = lambda v: spmv_shard(mb, v, axis, overlap=overlap)
        # full-precision matmuls for the Gram algebra: the TPU's default
        # for f32 is one bf16 pass, too coarse for block CG to converge
        with overlap_default(overlap), jax.default_matmul_precision("highest"):
            X, iters, it_cols, rr, bb = _block_hs_body(A, Bv[0], X0[0], **kw)
        return X[None], iters, it_cols, rr, bb

    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            mat_specs,
            P(axis, None, None),
            P(axis, None, None),
        ),
        out_specs=(P(axis, None, None), P(), P(), P(), P()),
        check_vma=False,  # loop carries are not annotated as shard-varying
    )

    @jax.jit
    def solve(mat_arg, Bv, X0):
        X, iters, it_cols, rr, bb = mapped(mat_arg, Bv, X0)
        return BlockSolveResult(
            x=X, iters=iters, iters_cols=it_cols, rr=rr, bb=bb
        )

    return partial(solve, mat)


def default_rhs_block(n: int, nrhs: int, dtype="float64"):
    """Deterministic (n, nrhs) RHS block with distinct, well-scaled columns.

    Column 0 is the all-ones vector the single-RHS benchmarks use; later
    columns add a small distinct sinusoid so the block is full-rank without
    changing the magnitude scale (keeps iteration counts comparable)."""
    import numpy as np

    i = np.arange(n, dtype=np.float64)
    cols = [
        np.ones(n) + 0.1 * j * np.sin((j + 1) * np.pi * (i + 0.5) / n)
        for j in range(nrhs)
    ]
    return np.stack(cols, axis=1).astype(dtype)


def solve_block_cg(mesh, mat: DistMat, B_np, *, x0_np=None, **kw):
    """Convenience host-level block solve: numpy (n, r) in, BlockSolveResult
    out."""
    import numpy as np

    from repro.core.partition import pad_block
    from repro.core.spmv import shard_vector

    Bp = pad_block(np.asarray(B_np), mat)
    Xp = (
        pad_block(np.asarray(x0_np), mat)
        if x0_np is not None
        else np.zeros_like(Bp)
    )
    solver = make_block_solver(mesh, mat, **kw)
    return solver(shard_vector(mesh, Bp), shard_vector(mesh, Xp))


# ---------------------------------------------------------------------------
# Session-reusable solver handles
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SolverHandle:
    """A compiled solver plus the energy trace captured at first warmup.

    Reuse subtlety: a jitted solver re-traces only on its *first* call —
    every later call is an XLA executable-cache hit, so wrapping it in
    ``trace.capture`` records nothing. The handle therefore snapshots the
    :class:`~repro.energy.trace.EnergyTrace` of the warmup call; repeat
    solves through the same handle integrate ledgers from that snapshot
    (the compiled program — hence its executed counts — cannot change
    without a new handle).

    The ``mesh``/``mat``/``precond`` references are load-bearing: the cache
    key uses their ``id()``, and holding them alive guarantees those ids
    are never recycled while the handle is cached.
    """

    fn: Callable
    key: tuple
    mesh: Any
    mat: Any
    precond: Any = None
    trace: Any = None  # EnergyTrace from the first warm(); None = cold

    @property
    def warmed(self) -> bool:
        return self.trace is not None

    def warm(self, *args):
        """Compile under the region trace on first use; no-op afterwards.

        Returns the warmup result (blocked until ready), or None when the
        handle is already warm. The compile work of the first call (trace
        and lowering, backend compile or persistent-cache load, cache hits
        and misses) is added to the ``warm_*`` counters of
        :data:`repro.obs.metrics.REGISTRY`."""
        if self.trace is not None:
            return None
        from repro.obs import metrics

        before = metrics.compile_totals()
        with jax.profiler.TraceAnnotation("repro.warm"):
            with trace.capture() as tr:
                res = self.fn(*args)
            jax.block_until_ready(res)
        after = metrics.compile_totals()
        for key, total in after.items():
            metrics.REGISTRY.counter(f"warm_{key}").inc(total - before[key])
        self.trace = tr
        return res

    def __call__(self, *args):
        """Dispatch one solve. The ``repro.solve`` profiler span covers the
        dispatch only: it ends before the device finishes."""
        with jax.profiler.TraceAnnotation("repro.solve"):
            return self.fn(*args)


#: Process-global handle cache for callers without a session. LRU-bounded:
#: each handle deliberately pins its mesh/mat/precond (see SolverHandle),
#: so an unbounded cache grows without limit in a long-running process.
#: Session-owned solves pass their own ``cache=`` dict instead — those
#: handles live exactly as long as the session (dropping the session frees
#: its compiled executables and partitions together).
_HANDLES: "collections.OrderedDict[tuple, SolverHandle]" = (
    collections.OrderedDict()
)
_HANDLE_LIMIT = 32


def set_solver_handle_limit(limit: int) -> int:
    """Set the global handle cache's LRU bound; returns the previous one."""
    global _HANDLE_LIMIT
    if limit < 1:
        raise ValueError(f"handle limit must be >= 1: {limit}")
    prev, _HANDLE_LIMIT = _HANDLE_LIMIT, int(limit)
    while len(_HANDLES) > _HANDLE_LIMIT:
        _HANDLES.popitem(last=False)
    return prev


def clear_solver_handles():
    """Drop every cached handle (frees the compiled executables; tests)."""
    _HANDLES.clear()


def solver_handle(
    mesh,
    mat: DistMat,
    *,
    op: str = "cg",
    nrhs: int = 1,
    variant: str = "hs",
    precond: Preconditioner | None = None,
    tol: float = 1e-8,
    maxiter: int = 100,
    s: int = 2,
    axis="shards",  # mesh axis name, or a (rows, cols) tuple for 2-D grids
    kernels: str | None = None,
    overlap: bool = True,
    telemetry: bool = False,
    cache: dict | None = None,
) -> SolverHandle:
    """Cached solver keyed by (partition, config): build once, solve many.

    Repeat requests for the same sharded ``mat`` (identity, not equality —
    a re-partition is a new program) and the same solver configuration
    return the already-compiled handle, skipping re-trace/re-compile
    entirely. Routes to :func:`make_block_solver` when ``nrhs`` > 1, the
    Ginkgo-analog baseline for ``variant="naive"``, the distributed SpMV
    for ``op="spmv"`` (``variant="naive"`` selects the all-gather SpMV),
    and :func:`make_solver` otherwise.

    ``cache`` scopes handle lifetime: pass an owner's dict (e.g. a
    ``SolverSession``'s) so its handles die with it; the default is the
    process-global LRU (:data:`_HANDLE_LIMIT` entries).
    """
    key = (
        id(mesh), id(mat), str(op), int(max(nrhs, 1)), str(variant),
        None if precond is None else id(precond),
        float(tol), int(maxiter), int(s), axis, kernels, bool(overlap),
        bool(telemetry),  # the callback is part of the compiled program
    )
    store = _HANDLES if cache is None else cache
    h = store.get(key)
    if (
        h is not None
        and h.mesh is mesh
        and h.mat is mat
        and (precond is None or h.precond is precond)
    ):
        if store is _HANDLES:
            _HANDLES.move_to_end(key)
        return h
    if op == "spmv":
        from repro.core.baselines import make_naive_spmv
        from repro.core.spmv import make_spmv

        if variant == "naive":
            fn = make_naive_spmv(mesh, mat, axis)
        else:
            fn = make_spmv(mesh, mat, axis, overlap=overlap)
    elif nrhs > 1:
        fn = make_block_solver(
            mesh, mat, precond=precond, tol=tol, maxiter=maxiter,
            axis=axis, kernels=kernels, overlap=overlap,
            telemetry=telemetry,
        )
    elif variant == "naive":
        from repro.core.baselines import make_naive_solver

        fn = make_naive_solver(
            mesh, mat, precond=precond, tol=tol, maxiter=maxiter, axis=axis
        )
    else:
        fn = make_solver(
            mesh, mat, variant=variant, precond=precond, tol=tol,
            maxiter=maxiter, s=s, axis=axis, kernels=kernels, overlap=overlap,
            telemetry=telemetry,
        )
    h = SolverHandle(fn=fn, key=key, mesh=mesh, mat=mat, precond=precond)
    store[key] = h
    if store is _HANDLES:
        while len(_HANDLES) > _HANDLE_LIMIT:
            _HANDLES.popitem(last=False)
    return h
