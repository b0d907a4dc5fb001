"""Backend-aware kernel dispatch for the solver hot path.

The repo carries three implementations of every hot-path op:

* ``pallas``    — the compiled Pallas TPU kernel (VMEM tiling, fused HBM
  passes). Only meaningful on a TPU backend, and f32 only: Mosaic has no
  64-bit types, so an explicit ``pallas`` choice raises on f64 operands.
* ``interpret`` — the same Pallas kernel run in interpret mode: exact kernel
  semantics on CPU, used by tests to validate the TPU code path. Refused on
  a TPU backend, where nothing may run interpreted.
* ``jnp``       — the pure-jnp reference (kernels/ref.py oracles). The
  default on CPU/GPU, where XLA fusion already does the right thing.

Selection: explicit argument > ``set_backend``/``use_backend`` override >
``REPRO_KERNELS`` env var > auto (TPU -> pallas, else jnp). Only auto
resolution may send an f64 call on a TPU to ``jnp``; every op records the
backend it resolved to in the active energy trace (the ledger's
``kernels`` block). Resolution happens at TRACE time — a jitted solver
bakes in whichever backend was active when it was traced; build a fresh
solver to switch.

Solvers obtain an :class:`OpSet` via :func:`ops_for` and call ops through
it. Every op invocation is recorded in the active :class:`SweepLedger`
(enabled with :func:`record_sweeps`), tagged with the current
:func:`ledger_section` — since ``lax.while_loop`` traces its body exactly
once, tracing a solver under the ledger yields the per-iteration HBM
sweep count directly. That is the accounting ``benchmarks/hotpath_fusion.py``
and the acceptance tests check: each vector op here streams its operands in
ONE pass, so "calls to vector ops per iteration" == "full-vector HBM sweeps
per iteration".
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from collections import Counter

import jax
import jax.numpy as jnp

from repro.energy import trace
from repro.energy.accounting import OpCounts
from repro.kernels import ref
from repro.kernels.fused_reductions import (
    _require_1d,
    block_gram,
    block_update,
    block_update2,
    fused_axpy,
    fused_axpy2,
    fused_axpy2_dots,
    fused_dots_n,
    sstep_basis,
    sstep_gram,
    sstep_update,
)
from repro.kernels.spmv_bcsr import (
    bcsr_finish_y,
    bcsr_finish_yb,
    bcsr_prepare_x,
    bcsr_prepare_xb,
)
from repro.kernels.spmv_bcsr import bcsr_spmm as _bcsr_spmm_kernel
from repro.kernels.spmv_bcsr import bcsr_spmv as _bcsr_spmv_kernel
from repro.kernels.spmv_stencil import (
    pick_bz,
    stencil_spmv_boundary,
    stencil_spmv_halo,
)

BACKENDS = ("pallas", "interpret", "jnp")
ENV_VAR = "REPRO_KERNELS"

# Ops that stream full-length vectors exactly once per call (1 sweep each).
# The block_* ops are the multi-RHS generalization: each call streams its
# (n, r) operand blocks once, so one call is still one sweep (of n*r
# elements per operand).
VECTOR_OPS = (
    "axpy", "fused_axpy2", "fused_axpy2_dots", "fused_dots_n",
    "block_gram", "block_update", "block_update2",
    "sstep_gram", "sstep_basis", "sstep_update",
)
# The SpMV is accounted separately (its traffic is the matrix term);
# stencil_boundary is the overlap path's two-plane edge fix-up; bcsr_spmv
# is the blocked interior matvec of the BCSR-format DistMat and bcsr_spmm
# its multi-RHS sibling.
SPMV_OPS = ("stencil_matvec", "stencil_boundary", "bcsr_spmv", "bcsr_spmm")

_override: str | None = None


def available_backend() -> str:
    """Auto resolution from the JAX backend."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _explicit(choice: str | None) -> str | None:
    """The first explicit backend in precedence order, or None (auto)."""
    for cand in (choice, _override, os.environ.get(ENV_VAR)):
        if cand is None:
            continue
        cand = cand.strip().lower()
        if cand in ("", "auto"):
            continue  # defer to the next precedence level
        if cand not in BACKENDS:
            raise ValueError(
                f"unknown kernel backend {cand!r}; want one of {BACKENDS} or 'auto'"
            )
        return cand
    return None


def resolve(choice: str | None = None) -> str:
    """Resolve a backend name: explicit > override > env > auto.

    ``None``/``''``/``'auto'`` at any level defers to the next one, so an
    explicit ``kernels='auto'`` still honors ``use_backend``/``REPRO_KERNELS``.
    """
    return _explicit(choice) or available_backend()


def backend() -> str:
    """The currently active backend (no explicit choice)."""
    return resolve(None)


def set_backend(name: str | None) -> None:
    """Process-wide override (None restores env/auto resolution)."""
    global _override
    if name is not None and name.strip().lower() not in BACKENDS + ("auto",):
        raise ValueError(f"unknown kernel backend {name!r}")
    _override = name


@contextlib.contextmanager
def use_backend(name: str | None):
    """Scoped override: ``with use_backend('interpret'): make_solver(...)``."""
    global _override
    prev = _override
    set_backend(name)
    try:
        yield
    finally:
        _override = prev


# ---------------------------------------------------------------------------
# Sweep ledger (tracing-time accounting)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SweepLedger:
    """Counts op calls per section during tracing.

    ``ops[section]`` maps op name -> number of calls; ``entries[section]``
    counts how many times the section was entered (normally 1 per trace —
    used to normalize if a body is retraced).
    """

    ops: dict = dataclasses.field(default_factory=dict)
    entries: dict = dataclasses.field(default_factory=dict)

    def count(self, section: str, name: str):
        self.ops.setdefault(section, Counter())[name] += 1

    def enter(self, section: str):
        self.entries[section] = self.entries.get(section, 0) + 1

    def vector_sweeps(self, section: str = "iteration") -> float:
        """Full-vector HBM sweeps per section entry (excludes the SpMV)."""
        c = self.ops.get(section, Counter())
        n = max(self.entries.get(section, 1), 1)
        return sum(v for k, v in c.items() if k in VECTOR_OPS) / n

    def spmv_calls(self, section: str = "iteration") -> float:
        c = self.ops.get(section, Counter())
        n = max(self.entries.get(section, 1), 1)
        return sum(v for k, v in c.items() if k in SPMV_OPS) / n


_ledger: SweepLedger | None = None
_section: str = "default"


@contextlib.contextmanager
def record_sweeps():
    """Activate a ledger; trace (lower/eval_shape) solvers inside."""
    global _ledger
    prev = _ledger
    _ledger = SweepLedger()
    try:
        yield _ledger
    finally:
        _ledger = prev


@contextlib.contextmanager
def ledger_section(name: str):
    """Tag ops traced inside with ``name`` (e.g. 'iteration').

    Also switches the energy-trace section (energy/trace.py), so the sweep
    ledger and the executed-counts region ledger stay in lockstep: both see
    the while_loop body as the per-iteration accounting unit.
    """
    global _section
    prev = _section
    _section = name
    if _ledger is not None:
        _ledger.enter(name)
    try:
        with trace.section(name):
            yield
    finally:
        _section = prev


def _record(name: str, counts: OpCounts | None = None):
    if _ledger is not None:
        _ledger.count(_section, name)
    if counts is not None:
        trace.record_op(name, counts)


# ---------------------------------------------------------------------------
# Op set
# ---------------------------------------------------------------------------


# executed-counts formulas shared with the other instrumented layers
_axpy_counts = trace.streamed_axpy_counts


class OpSet:
    """Hot-path ops bound to one backend. Obtain via :func:`ops_for`."""

    def __init__(self, backend_name: str, *, chunk: int = 65536,
                 auto: bool = False):
        assert backend_name in BACKENDS
        if backend_name == "interpret" and jax.default_backend() == "tpu":
            raise ValueError(
                "kernels='interpret' runs the Pallas kernels on the host; "
                "on a TPU backend use 'pallas' (or 'auto')"
            )
        self.backend = backend_name
        self.chunk = chunk
        self.auto = auto

    def __repr__(self):
        return f"OpSet(backend={self.backend!r})"

    def _mode(self, op: str, dtype) -> str:
        """The backend ``op`` runs on for operands of ``dtype``.

        Mosaic has no 64-bit types: under auto resolution an f64 call runs
        the jnp reference, an explicit ``pallas`` choice raises. The choice
        is recorded in the active energy trace."""
        b = self.backend
        if b == "pallas" and jnp.dtype(dtype).itemsize == 8:
            if not self.auto:
                raise ValueError(
                    f"kernels='pallas' cannot run {op} on {jnp.dtype(dtype)} "
                    "operands: the TPU kernel compiler has no 64-bit types. "
                    "Solve in f32 (x64=False) or use kernels='auto'/'jnp'."
                )
            b = "jnp"
        trace.record_kernel(op, b)
        return b

    # -- fused vector ops (1 HBM sweep each) --------------------------------

    def axpy(self, a, x, y):
        """``a*x + y`` for a scalar ``a`` and (n,) vectors ``x``/``y``.

        One fused HBM pass: 2n flops, 3n elements streamed (read x, y;
        write the result). Returns the (n,) updated vector.
        """
        _require_1d("axpy", x, y)
        _record("axpy", _axpy_counts(x.size, x.dtype.itemsize))
        b = self._mode("axpy", x.dtype)
        if b == "jnp":
            return ref.fused_axpy_ref(a, x, y)
        return fused_axpy(a, x, y, chunk=self.chunk,
                          interpret=(b == "interpret"))

    def fused_axpy2(self, a1, x1, y1, a2, x2, y2):
        """``(a1*x1 + y1, a2*x2 + y2)`` — two independent axpys, ONE pass.

        The two updates may not feed each other (they are evaluated from
        the inputs as given). Returns the pair of (n,) results; counts as a
        single HBM sweep of 6n streamed elements / 4n flops.
        """
        _require_1d("fused_axpy2", x1, y1, x2, y2)
        _record("fused_axpy2", _axpy_counts(x1.size, x1.dtype.itemsize, 2))
        b = self._mode("fused_axpy2", x1.dtype)
        if b == "jnp":
            return ref.fused_axpy2_ref(a1, x1, y1, a2, x2, y2)
        return fused_axpy2(a1, x1, y1, a2, x2, y2, chunk=self.chunk,
                           interpret=(b == "interpret"))

    def fused_axpy2_dots(self, a1, x1, y1, a2, x2, y2):
        """``(a1*x1+y1, a2*x2+y2, [o2·o2])`` in ONE pass.

        The hs-update special: both axpys plus the *local* squared norm of
        the second output (a (1,) array — callers ``psum`` it), computed
        while the operands are already streaming. Same HBM traffic as
        :meth:`fused_axpy2`, +2n flops.
        """
        _require_1d("fused_axpy2_dots", x1, y1, x2, y2)
        n, ib = x1.size, x1.dtype.itemsize
        # two fused updates + the in-flight dot of the second output (no
        # extra HBM pass — the operands are already streaming).
        _record(
            "fused_axpy2_dots",
            _axpy_counts(n, ib, 2) + OpCounts(flops=2.0 * n),
        )
        b = self._mode("fused_axpy2_dots", x1.dtype)
        if b == "jnp":
            return ref.fused_axpy2_dots_ref(a1, x1, y1, a2, x2, y2)
        return fused_axpy2_dots(a1, x1, y1, a2, x2, y2, chunk=self.chunk,
                                interpret=(b == "interpret"))

    def fused_dots_n(self, pairs):
        """Local partial dots ``[(x, y), ...] -> (len(pairs),)``, ONE pass.

        Repeated operands are deduplicated (each distinct vector is
        streamed once), so e.g. the fcg triple ``[(r,u),(w,u),(r,r)]`` with
        ``u is r`` reads only {r, w}. Results are LOCAL partial sums — the
        caller packs them into a single ``lax.psum``.
        """
        _require_1d("fused_dots_n", *[a for p in pairs for a in p])
        _record("fused_dots_n", trace.local_dots_counts(pairs))
        b = self._mode("fused_dots_n", pairs[0][0].dtype)
        if b == "jnp":
            return ref.fused_dots_n_ref(pairs)
        return fused_dots_n(pairs, chunk=self.chunk,
                            interpret=(b == "interpret"))

    # -- multi-RHS block ops (1 HBM sweep each) -----------------------------

    def block_gram(self, pairs):
        """Local Gram blocks ``[Xᵀ @ Y, ...]`` for (n, r) pairs, ONE pass.

        The block-CG reduction primitive: each distinct operand block is
        streamed once, the (r, r) accumulators stay resident. Results are
        LOCAL — callers pack them into a single psum (`fused_blocks`).
        Order-sensitive (XᵀY != YᵀX), unlike the scalar dots.
        """
        _record("block_gram", trace.block_gram_counts(pairs))
        b = self._mode("block_gram", pairs[0][0].dtype)
        if b == "jnp":
            return ref.block_gram_ref(pairs)
        return block_gram(pairs, interpret=(b == "interpret"))

    def block_update(self, m, x, y, mask=None):
        """``y * mask + x @ m`` for (n, r) blocks and an (r, r) coefficient
        block; ``mask`` is an optional (r,) column scale (the deflation
        mask) folded into the same pass. One sweep: read x, y; write o.
        """
        n, r = x.shape
        _record("block_update", trace.block_update_counts(
            n, r, x.dtype.itemsize))
        b = self._mode("block_update", x.dtype)
        if b == "jnp":
            return ref.block_update_ref(m, x, y, mask)
        # the (n, r) kernels keep their own row chunk: ``self.chunk`` sizes
        # 1-D vector blocks, and (chunk, r) tiles are padded to 128 lanes
        return block_update(m, x, y, mask, interpret=(b == "interpret"))

    def block_update2(self, a1, x1, y1, a2, x2, y2):
        """``(y1 + x1 @ a1, y2 + x2 @ a2)`` — the block-CG X/R update pair
        in ONE pass over all four (n, r) blocks."""
        n, r = x1.shape
        _record("block_update2", trace.block_update_counts(
            n, r, x1.dtype.itemsize, terms=2))
        b = self._mode("block_update2", x1.dtype)
        if b == "jnp":
            return ref.block_update2_ref(a1, x1, y1, a2, x2, y2)
        return block_update2(a1, x1, y1, a2, x2, y2,
                             interpret=(b == "interpret"))

    # -- s-step block ops (1 HBM sweep each) --------------------------------

    def sstep_gram(self, pb, wb, wp, r):
        """Local s-step reduction ``[PᵀW | WpᵀP | Pᵀr | rᵀr]`` as one flat
        (2s² + s + 1,) vector, ONE pass over {P, W, Wp, r}.

        Everything the s-step block solve needs from the data — both Gram
        blocks, the moment vector, and the residual norm — as LOCAL partial
        sums the caller psums once (`fused_blocks`). The basis column
        A-norms for the stability scaling are ``diag(PᵀW)``, so the
        collective payload matches the unscaled algorithm exactly.
        """
        n, s = pb.shape
        ib = pb.dtype.itemsize
        _record(
            "sstep_gram",
            OpCounts(
                flops=float(4 * n * s * s + 2 * n * s + 2 * n),
                hbm_bytes=float((3 * s + 1) * n + 2 * s * s + s + 1) * ib,
            ),
        )
        b = self._mode("sstep_gram", pb.dtype)
        if b == "jnp":
            return ref.sstep_gram_ref(pb, wb, wp, r)
        return sstep_gram(pb, wb, wp, r, interpret=(b == "interpret"))

    def sstep_basis(self, b, dinv, qp, pb, wp, wb):
        """``(Pb·diag(dinv) − Qp @ b, Wb·diag(dinv) − Wp @ b)`` — the
        normalized A-conjugated search/image blocks, ONE pass over all four
        (n, s) blocks (read 4, write 2)."""
        n, s = pb.shape
        ib = pb.dtype.itemsize
        _record(
            "sstep_basis",
            OpCounts(
                flops=float(4 * n * s * s + 4 * n * s),
                hbm_bytes=6.0 * n * s * ib,
            ),
        )
        bk = self._mode("sstep_basis", pb.dtype)
        if bk == "jnp":
            return ref.sstep_basis_ref(b, dinv, qp, pb, wp, wb)
        return sstep_basis(b, dinv, qp, pb, wp, wb,
                           interpret=(bk == "interpret"))

    def sstep_update(self, a, q, wq, x, r):
        """``(x + Q @ a, r − WQ @ a)`` for an (s,) coefficient vector — the
        s-step x/r update, ONE pass over both blocks and both vectors."""
        n, s = q.shape
        ib = q.dtype.itemsize
        _record(
            "sstep_update",
            OpCounts(
                flops=float(4 * n * s + 2 * n),
                hbm_bytes=float(2 * n * s + 4 * n) * ib,
            ),
        )
        b = self._mode("sstep_update", q.dtype)
        if b == "jnp":
            return ref.sstep_update_ref(a, q, wq, x, r)
        return sstep_update(a, q, wq, x, r, interpret=(b == "interpret"))

    # -- SpMV ---------------------------------------------------------------

    def stencil_matvec(self, x3, prev_halo, next_halo, *, stencil="7pt",
                       aniso=(1.0, 1.0, 1.0)):
        """Local-slab matrix-free SpMV with explicit z-halo planes.

        Args: ``x3`` the (nz_loc, ny, nx) slab, ``prev_halo``/``next_halo``
        the (ny, nx) neighbor boundary planes (zeros at the global edges).
        Returns the (nz_loc, ny, nx) product. Accounted as one full-slab
        HBM sweep plus the two halo planes (matrix-free: no value/index
        traffic).
        """
        n, ib = x3.size, x3.dtype.itemsize
        k = {"7pt": 7, "27pt": 27}[stencil]
        # matrix-free: NO matrix-value/index traffic — read the slab + both
        # halo planes once, write the result slab once.
        _record(
            "stencil_matvec",
            OpCounts(
                flops=2.0 * k * n,
                hbm_bytes=float(n + prev_halo.size + next_halo.size + n) * ib,
            ),
        )
        b = self._mode("stencil_matvec", x3.dtype)
        if b == "jnp":
            return ref.stencil_halo_ref(
                x3, prev_halo, next_halo, stencil=stencil, aniso=aniso
            )
        return stencil_spmv_halo(
            x3, prev_halo, next_halo, stencil=stencil, aniso=aniso,
            bz=pick_bz(x3.shape[0]), interpret=(b == "interpret"),
        )

    def bcsr_spmv(self, blocks, bcol, x, *, n_brows, bpr, n_out=None):
        """Uniform-layout block-CSR SpMV (the BCSR DistMat interior).

        ``blocks`` is the (n_brows*bpr, br, bc) dense-block array and
        ``bcol`` its block-column ids (``core.sparse.pack_bcsr`` layout,
        padding blocks all-zero with ``bcol == 0``). ``x`` may be the
        native (n_bcols, bc) tile layout or a flat (n,) vector — flat
        inputs are zero-padded up to the block grid and returned flat,
        trimmed to ``n_out``. Accounted as one streaming pass over blocks
        + block ids + the source vector, writing the blocked result.
        """
        _, br, bc = blocks.shape
        b = x.dtype.itemsize
        mat_bytes = float(blocks.size * b + bcol.size * bcol.dtype.itemsize)
        _record(
            "bcsr_spmv",
            OpCounts(
                flops=2.0 * blocks.size,
                hbm_bytes=mat_bytes + float(x.size * b + n_brows * br * b),
                hbm_matrix_bytes=mat_bytes,
            ),
        )
        backend_name = self._mode("bcsr_spmv", x.dtype)
        x, flat, n_out = bcsr_prepare_x(
            blocks, x, n_brows=n_brows, bpr=bpr, n_out=n_out
        )
        if backend_name == "jnp":
            y = ref.bcsr_spmv_ref(blocks, bcol, x, n_brows, bpr)
        else:
            y = _bcsr_spmv_kernel(
                blocks, bcol, x, n_brows=n_brows, bpr=bpr,
                interpret=(backend_name == "interpret"),
            )
        return bcsr_finish_y(y, flat, n_out)

    def bcsr_spmm(self, blocks, bcol, x, *, n_brows, bpr, n_out=None):
        """Multi-RHS :meth:`bcsr_spmv`: ``x`` is an (n, r) RHS block (or
        the native (n_bcols, bc, r) tile layout). The matrix blocks and
        ids are streamed ONCE while vector traffic scales with ``r`` — the
        amortization the multi-RHS solver exists for, visible in the
        recorded ``hbm_matrix_bytes``."""
        _, br, bc = blocks.shape
        r = x.shape[-1]
        b = x.dtype.itemsize
        mat_bytes = float(blocks.size * b + bcol.size * bcol.dtype.itemsize)
        _record(
            "bcsr_spmm",
            OpCounts(
                flops=2.0 * blocks.size * r,
                hbm_bytes=mat_bytes + float(x.size * b + n_brows * br * r * b),
                hbm_matrix_bytes=mat_bytes,
            ),
        )
        backend_name = self._mode("bcsr_spmm", x.dtype)
        x, flat, n_out = bcsr_prepare_xb(
            blocks, x, n_brows=n_brows, bpr=bpr, n_out=n_out
        )
        if backend_name == "jnp":
            y = ref.bcsr_spmm_ref(blocks, bcol, x, n_brows, bpr)
        else:
            y = _bcsr_spmm_kernel(
                blocks, bcol, x, n_brows=n_brows, bpr=bpr,
                interpret=(backend_name == "interpret"),
            )
        return bcsr_finish_yb(y, flat, n_out)

    def stencil_boundary(self, x3, prev_halo, next_halo, *, stencil="7pt",
                         aniso=(1.0, 1.0, 1.0)):
        """First + last output planes of the slab SpMV (overlap fix-up).

        The communication-hiding stencil path runs :meth:`stencil_matvec`
        with zero halos while the ppermute is in flight, then patches the
        two slab-edge output planes with this op once the halo planes
        arrive. Args as in :meth:`stencil_matvec` (``x3.shape[0] >= 2``);
        returns (2, ny, nx): output planes 0 and nz_loc-1, bitwise equal to
        the serialized single-call planes. Accounted as plane-sized traffic
        only (6 planes read, 2 written).
        """
        n_pl, ib = prev_halo.size, x3.dtype.itemsize
        k = {"7pt": 7, "27pt": 27}[stencil]
        _record(
            "stencil_boundary",
            OpCounts(flops=2.0 * k * 2 * n_pl, hbm_bytes=8.0 * n_pl * ib),
        )
        b = self._mode("stencil_boundary", x3.dtype)
        if b == "jnp":
            return ref.stencil_boundary_ref(
                x3, prev_halo, next_halo, stencil=stencil, aniso=aniso
            )
        return stencil_spmv_boundary(
            x3, prev_halo, next_halo, stencil=stencil, aniso=aniso,
            interpret=(b == "interpret"),
        )


def ops_for(kernels: str | None = None, *, chunk: int = 65536) -> OpSet:
    """Resolve a backend choice into a bound :class:`OpSet`.

    ``kernels``: None/'auto' (resolve from override/env/backend) or one of
    ``BACKENDS``. Solver factories thread their ``kernels=`` argument here.
    """
    name = _explicit(kernels)
    return OpSet(name or available_backend(), chunk=chunk, auto=name is None)
