"""Block-row partitioning + halo-exchange planning (host side).

This module reproduces the paper's distribution substrate (C1):

* matrices are distributed in **blocks of contiguous rows** across shards;
* device-resident column indices are **4-byte local indices** obtained by a
  global->local shift + compaction — the global (possibly >2^32) index space
  exists only on the host at partition time (numpy ``int64``);
* every shard's sparse rows are split into an **interior block** (entries
  whose column is owned by the shard — no communication needed) and a compact
  **boundary block** holding only the ghost-touching rows' external entries,
  so that the halo ``ppermute`` can be issued first, the interior matvec runs
  while the exchange is in flight, and the boundary block is applied on
  arrival — the JAX analog of BootCMatchGX's overlap of GPU compute with MPI
  communication (see ``core/spmv.spmv_shard`` and ``docs/architecture.md``);
* the halo exchange itself is planned as a set of ``lax.ppermute`` shifts
  ("ring" mode, for matrices whose off-shard couplings reach at most
  ``max_ring`` neighbor shards — all banded/stencil problems) or falls back to
  a full ``all_gather`` ("allgather" mode) for irregular coupling patterns.
  The fallback mirrors the paper's observation that irregular matrices
  (G3_circuit-like) lose scalability to communication.

Everything here is numpy / scipy; the device-side execution lives in
``core/spmv.py``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import numpy as np


# ---------------------------------------------------------------------------
# Row partition
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RowPartition:
    """Contiguous block-row partition of ``n_global`` rows over ``n_shards``."""

    n_global: int
    row_starts: tuple[int, ...]  # length n_shards + 1, row_starts[-1] == n_global

    @property
    def n_shards(self) -> int:
        return len(self.row_starts) - 1

    def owner_range(self, shard: int) -> tuple[int, int]:
        return self.row_starts[shard], self.row_starts[shard + 1]

    def n_own(self, shard: int) -> int:
        lo, hi = self.owner_range(shard)
        return hi - lo

    @property
    def max_own(self) -> int:
        return max(self.n_own(s) for s in range(self.n_shards))

    def owner_of(self, gcol: np.ndarray) -> np.ndarray:
        """Shard owning each global column (vectorized)."""
        starts = np.asarray(self.row_starts[1:], dtype=np.int64)
        return np.searchsorted(starts, gcol, side="right").astype(np.int64)


def balanced_partition(n_global: int, n_shards: int) -> RowPartition:
    starts = np.linspace(0, n_global, n_shards + 1).astype(np.int64)
    return RowPartition(n_global, tuple(int(s) for s in starts))


def plane_partition(n_global: int, plane: int, n_shards: int) -> RowPartition:
    """Partition along whole z-planes of size ``plane`` (stencil slabs)."""
    nz = n_global // plane
    assert nz * plane == n_global, "n_global must be a multiple of plane"
    if nz < n_shards:
        raise ValueError(f"cannot slab-partition nz={nz} over {n_shards} shards")
    zs = np.linspace(0, nz, n_shards + 1).astype(np.int64)
    return RowPartition(n_global, tuple(int(z) * plane for z in zs))


def default_grid(n_shards: int) -> tuple[int, int]:
    """Most-square ``(rows, cols)`` factorization with ``rows <= cols``.

    4 -> (2, 2), 8 -> (2, 4), 16 -> (4, 4), 32 -> (4, 8). Primes (and
    shard counts below 4) have no nontrivial factorization and map to
    ``(1, n_shards)`` — the 1-D layout.
    """
    n_shards = int(n_shards)
    r = max(int(np.sqrt(n_shards)), 1)
    while r > 1 and n_shards % r:
        r -= 1
    return (r, n_shards // r)


def pencil_partition(p, grid: tuple[int, int]) -> tuple[np.ndarray, RowPartition]:
    """Pencil (z-block x y-block) row ordering for an ``R x C`` process grid.

    Returns ``(perm, part)``: ``perm[new] = old`` is the symmetric row
    permutation that makes the flat shard ``s = i*C + j`` own the pencil
    ``z_blocks[i] x y_blocks[j] x [0, nx)`` as one contiguous row block, and
    ``part`` is the matching :class:`RowPartition`. Solving the permuted
    system ``A[perm][:, perm] x' = b[perm]`` with ``partition_csr(...,
    grid=grid, partition=part)`` gives per-dimension halos that scale with
    the pencil *surface* (``O(N^2 / sqrt(S))`` per shard), not the slab
    cross-section (``O(N^2)``) — the 2-D decomposition's whole point.

    ``p`` is duck-typed: it only needs ``nx``/``ny``/``nz`` (``PoissonProblem``
    qualifies). Empty z-blocks / y-blocks (grid larger than the axis) yield
    empty shards, which the partitioner handles.
    """
    gr, gc = int(grid[0]), int(grid[1])
    z_blocks = np.array_split(np.arange(p.nz, dtype=np.int64), gr)
    y_blocks = np.array_split(np.arange(p.ny, dtype=np.int64), gc)
    xs = np.arange(p.nx, dtype=np.int64)
    parts, starts, tot = [], [0], 0
    for zb in z_blocks:
        for yb in y_blocks:
            zz, yy, xx = np.meshgrid(zb, yb, xs, indexing="ij")
            ids = (xx + p.nx * (yy + p.ny * zz)).ravel()
            parts.append(ids)
            tot += ids.size
            starts.append(tot)
    perm = (
        np.concatenate(parts) if parts else np.zeros(0, np.int64)
    ).astype(np.int64)
    return perm, RowPartition(p.nx * p.ny * p.nz, tuple(starts))


# ---------------------------------------------------------------------------
# Halo plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HaloPlan:
    """Static description of a halo exchange.

    mode == "ring":
        ``shifts[k]`` means every shard i *receives* a buffer of width
        ``widths[k]`` from shard ``i + shifts[k]`` (edge shards receive
        zeros).  The receive buffers are concatenated after ``x_own`` in
        shift order, forming ``x_ext = [x_own | buf_0 | buf_1 | ...]``.
    mode == "allgather":
        ``x_ext`` is the full (padded) global vector, ``all_gather``-ed
        over the shard axis; widths/shifts are empty.
    """

    mode: str  # "ring" | "allgather"
    shifts: tuple[int, ...]
    widths: tuple[int, ...]
    n_own_pad: int  # uniform padded rows per shard
    n_shards: int

    @property
    def ext_len(self) -> int:
        if self.mode == "allgather":
            return self.n_own_pad * self.n_shards
        return self.n_own_pad + sum(self.widths)

    def buf_offset(self, k: int) -> int:
        """Offset of receive buffer ``k`` inside x_ext (ring mode)."""
        return self.n_own_pad + sum(self.widths[:k])

    def perm(self, k: int) -> tuple[tuple[int, int], ...]:
        """ppermute (src, dst) pairs for shift k: src j sends to j - shift."""
        d = self.shifts[k]
        return tuple(
            (j, j - d) for j in range(self.n_shards) if 0 <= j - d < self.n_shards
        )

    def collective_bytes_per_shard(self, itemsize: int = 8) -> int:
        """Bytes each shard sends per exchange (roofline collective term)."""
        if self.mode == "allgather":
            return self.n_own_pad * (self.n_shards - 1) * itemsize
        return sum(self.widths) * itemsize


@dataclasses.dataclass(frozen=True)
class GridPlan:
    """Static halo-exchange description for a 2-D ``R x C`` process grid.

    Shards are laid out flat-row-major over the grid: flat shard
    ``s = i * C + j`` sits at grid position ``(i, j)``. Rows stay
    block-contiguous over the *flat* shard order (so the padded vector
    layout is identical to the 1-D one); what changes is the neighbor
    structure: ``shifts[k] = (di, dj)`` means shard ``(i, j)`` *receives* a
    buffer of width ``widths[k]`` from shard ``(i + di, j + dj)`` (edge
    shards receive zeros). Receive buffers concatenate after ``x_own`` in
    shift order, exactly like :class:`HaloPlan` ring mode.

    Each shift moves per dimension: a pure-column shift ``(0, dj)`` is one
    ``ppermute`` over the mesh's ``cols`` axis, a pure-row shift ``(di, 0)``
    one over ``rows``, and a corner shift ``(di, dj)`` chains the two (the
    column hop first, then the row hop forwards the received buffer), i.e.
    ``hops(k)`` ppermute launches and that many traversals of the buffer
    over the interconnect.
    """

    mode: str  # always "grid"
    grid: tuple[int, int]  # (rows, cols) of the process grid
    shifts: tuple[tuple[int, int], ...]  # (di, dj) receive-from deltas
    widths: tuple[int, ...]
    n_own_pad: int  # uniform padded rows per shard
    n_shards: int

    #: Mesh axis names the exchange runs over, in (rows, cols) order.
    axes: tuple[str, str] = ("rows", "cols")

    @property
    def ext_len(self) -> int:
        return self.n_own_pad + sum(self.widths)

    def buf_offset(self, k: int) -> int:
        """Offset of receive buffer ``k`` inside x_ext."""
        return self.n_own_pad + sum(self.widths[:k])

    def hops(self, k: int) -> int:
        """Interconnect hops of shift ``k`` (1 pure-axis, 2 corner)."""
        di, dj = self.shifts[k]
        return int(di != 0) + int(dj != 0)

    def perm_rows(self, k: int) -> tuple[tuple[int, int], ...]:
        """ppermute (src, dst) pairs over the ``rows`` axis for shift k."""
        di = self.shifts[k][0]
        gr = self.grid[0]
        return tuple((i, i - di) for i in range(gr) if 0 <= i - di < gr)

    def perm_cols(self, k: int) -> tuple[tuple[int, int], ...]:
        """ppermute (src, dst) pairs over the ``cols`` axis for shift k."""
        dj = self.shifts[k][1]
        gc = self.grid[1]
        return tuple((j, j - dj) for j in range(gc) if 0 <= j - dj < gc)

    @property
    def n_launches(self) -> int:
        """Total ppermute launches per exchange (corners count twice)."""
        return sum(self.hops(k) for k in range(len(self.shifts)))

    def dim_bytes_per_shard(self, itemsize: int = 8) -> tuple[int, int]:
        """(rows_bytes, cols_bytes) each shard moves per exchange.

        A corner shift traverses both dimensions, so its width counts in
        both entries; the sum of the two equals
        :meth:`collective_bytes_per_shard`.
        """
        rows_b = sum(
            w * itemsize for (di, _), w in zip(self.shifts, self.widths) if di
        )
        cols_b = sum(
            w * itemsize for (_, dj), w in zip(self.shifts, self.widths) if dj
        )
        return rows_b, cols_b

    def collective_bytes_per_shard(self, itemsize: int = 8) -> int:
        """Bytes each shard moves per exchange (hop-weighted: a corner
        buffer crosses two links)."""
        return sum(
            self.hops(k) * w * itemsize for k, w in enumerate(self.widths)
        )


# ---------------------------------------------------------------------------
# Interior storage blocks (format-polymorphic) + the distributed matrix
# ---------------------------------------------------------------------------


def _register(cls, data_fields, meta_fields):
    return partial(
        jax.tree_util.register_dataclass,
        data_fields=data_fields,
        meta_fields=meta_fields,
    )(cls)


def _host(a) -> np.ndarray:
    """A global (S, ...) array as host numpy in the dtype JAX would give it.

    Partition outputs stay on the host until ``spmv.shard_matrix`` places
    each shard's block on its own device; staging the whole matrix through
    ``jnp.asarray`` would first copy all of it onto the default device."""
    a = np.asarray(a)
    return a.astype(jax.dtypes.canonicalize_dtype(a.dtype), copy=False)


def _size(a) -> int:
    """Element count from the static shape (works for ShapeDtypeStruct)."""
    return int(np.prod(a.shape, dtype=np.int64))


FORMATS = ("ell", "hyb", "bcsr")


@partial(_register, data_fields=("data", "col"), meta_fields=())
@dataclasses.dataclass(frozen=True)
class ELLBlock:
    """Padded-ELL interior: (S, R, k) slots/row, padding data == 0, col == 0.

    The historical (and stencil-optimal) layout: every row gets
    ``k = max_row_nnz`` slots, so one long row inflates the storage of every
    row on every shard — exactly the blowup HYB exists to avoid.
    """

    data: jax.Array  # (S, R, k)
    col: jax.Array  # (S, R, k) int32, indexes x_own

    fmt = "ell"

    @property
    def slots(self) -> int:
        """Stored value slots, padding included."""
        return _size(self.data)

    @property
    def index_bytes(self) -> int:
        return _size(self.col) * 4

    @property
    def k(self) -> int:
        return self.data.shape[-1]


@partial(
    _register,
    data_fields=("data", "col", "tail_data", "tail_col", "tail_row"),
    meta_fields=("n_tail",),
)
@dataclasses.dataclass(frozen=True)
class HYBBlock:
    """Hybrid interior: dense ELL prefix + COO tail for the long rows.

    The first ``k_typ`` entries of every row live in the (S, R, k_typ) ELL
    part; the overflow of the few rows longer than ``k_typ`` lives in a
    (S, T) COO tail applied by scatter-add. ``k_typ`` is chosen by the
    stored-bytes cost model (``roofline/format_model.hyb_split``), which is
    what eliminates the ``k = max_row_nnz`` padding blowup on power-law
    matrices. Padding: data == 0, col == 0, tail_row == 0 (exact-zero adds).
    """

    data: jax.Array  # (S, R, k_typ)
    col: jax.Array  # (S, R, k_typ) int32
    tail_data: jax.Array  # (S, T)
    tail_col: jax.Array  # (S, T) int32, indexes x_own
    tail_row: jax.Array  # (S, T) int32, local destination row
    n_tail: tuple[int, ...] = ()  # genuine tail entries per shard (host meta)

    fmt = "hyb"

    @property
    def slots(self) -> int:
        return _size(self.data) + _size(self.tail_data)

    @property
    def index_bytes(self) -> int:
        # ELL part: one col id per slot; tail: col + destination row.
        return _size(self.col) * 4 + _size(self.tail_data) * 8

    @property
    def k_typ(self) -> int:
        return self.data.shape[-1]


@partial(
    _register,
    data_fields=("blocks", "bcol"),
    meta_fields=("n_brows", "bpr", "br", "bc"),
)
@dataclasses.dataclass(frozen=True)
class BCSRBlock:
    """Blocked interior: dense (br, bc) tiles in the Pallas kernel's uniform
    blocks-per-row layout (``core.sparse.pack_bcsr``).

    One block-column id per *block* instead of per entry — the index-traffic
    win on banded/FEM matrices — at the price of storing zero fill inside
    partially-populated tiles. The SpMV routes through the kernel dispatch
    op ``bcsr_spmv`` (kernels/dispatch.py), running the Pallas block kernel
    inside shard_map on TPU/interpret backends.
    """

    blocks: jax.Array  # (S, n_brows * bpr, br, bc)
    bcol: jax.Array  # (S, n_brows * bpr) int32, block-column ids
    n_brows: int
    bpr: int
    br: int
    bc: int

    fmt = "bcsr"

    @property
    def slots(self) -> int:
        return _size(self.blocks)

    @property
    def index_bytes(self) -> int:
        return _size(self.bcol) * 4


InteriorBlock = ELLBlock | HYBBlock | BCSRBlock


@partial(
    _register,
    data_fields=(
        "interior",
        "data_ext",
        "col_ext",
        "bnd_rows",
        "send_sel",
        "ghost_data",
        "ghost_col",
        "ghost_pos",
    ),
    meta_fields=("plan", "n_global", "row_starts", "n_bnd", "halo_depth"),
)
@dataclasses.dataclass(frozen=True)
class DistMat:
    """Block-row-distributed sparse matrix: format-polymorphic interior +
    format-agnostic compact boundary block.

    All arrays carry a leading ``n_shards`` axis (sharded over the solver
    mesh's ``shards`` axis outside shard_map; squeezed to the local block
    inside).

    * ``interior``          — the per-shard **interior block** (entries whose
      column is owned by the same shard, indexing ``x_own`` of length
      R = n_own_pad; no communication needed), stored as one of
      :class:`ELLBlock` / :class:`HYBBlock` / :class:`BCSRBlock` — chosen
      per matrix by the ``fmt`` argument of the builders, or by the
      stored-bytes cost model under ``fmt="auto"``
      (``roofline/format_model.py``).
    * ``data_ext/col_ext``  — (S, B, k_ext): the **boundary block** — the
      external (ghost-column) entries of the B = n_boundary ghost-touching
      rows only, compacted at partition time; ``col_ext`` indexes ``x_ext``
      (see HaloPlan). Row ``j`` of the block belongs to local row
      ``bnd_rows[:, j]``. Always ELL — it is tiny and format choice only
      concerns the interior.
    * ``bnd_rows``          — (S, B) int32: local row id of each boundary-block
      row; slots past ``n_bnd[s]`` are padding (index 0, zero data — a
      scatter-add of exact zeros).
    * ``n_bnd``             — per-shard count of genuine boundary rows (host
      metadata; the device path never needs it, ``expand_boundary`` does).
    * ``send_sel``          — (S, sum(widths)) int32: per shift k, the slice
      ``send_sel[:, off_k : off_k + widths[k]]`` lists the local indices each
      shard sends for that shift.
    * ``ghost_data/ghost_col/ghost_pos`` — the **ghost-row block** carried
      only by deep-halo partitions (``halo_depth > 1``): the sparse rows of
      the depth ``< halo_depth`` ghost columns, replicated onto the shard so
      ``core/spmv.matrix_powers`` can redundantly recompute the halo region
      between chained SpMV applications instead of re-exchanging.
      ``ghost_data/ghost_col`` are (S, G, kg) padded-ELL rows whose column
      ids index ``x_ext``; ``ghost_pos`` (S, G) is each ghost row's own
      position inside ``x_ext`` (the halo slot its recomputed value scatters
      back into). Padding rows carry ``ghost_pos == ext_len`` (an
      out-of-range scatter, dropped on device). Depth-1 matrices carry
      0-sized ghost arrays.
    * ``halo_depth``        — ghost-zone depth ``k``: one widened exchange
      delivers the transitive closure of the boundary coupling to depth k,
      enough to chain k SpMV applications locally.
    Padding: data == 0, col == 0 everywhere (gathers stay in bounds and
    contribute nothing).
    """

    interior: InteriorBlock
    data_ext: jax.Array
    col_ext: jax.Array
    bnd_rows: jax.Array
    send_sel: jax.Array
    plan: HaloPlan | GridPlan
    n_global: int
    row_starts: tuple[int, ...]
    n_bnd: tuple[int, ...] = ()
    ghost_data: jax.Array | None = None
    ghost_col: jax.Array | None = None
    ghost_pos: jax.Array | None = None
    halo_depth: int = 1

    @property
    def fmt(self) -> str:
        """Interior storage format: 'ell' | 'hyb' | 'bcsr'."""
        return self.interior.fmt

    @property
    def n_shards(self) -> int:
        return self.plan.n_shards

    @property
    def n_own_pad(self) -> int:
        return self.plan.n_own_pad

    @property
    def n_boundary(self) -> int:
        """Padded boundary-block rows per shard (B)."""
        return self.bnd_rows.shape[-1]

    @property
    def n_ghost_rows(self) -> int:
        """Padded ghost-row-block rows per shard (G; 0 unless deep halo)."""
        return 0 if self.ghost_pos is None else self.ghost_pos.shape[-1]

    @property
    def ghost_slots(self) -> int:
        """Stored ghost-row value slots (padding included, all shards)."""
        return 0 if self.ghost_data is None else _size(self.ghost_data)

    @property
    def dtype(self):
        return (
            self.interior.blocks.dtype
            if isinstance(self.interior, BCSRBlock)
            else self.interior.data.dtype
        )

    # -- ELL back-compat views ----------------------------------------------

    @property
    def data_loc(self) -> jax.Array:
        """(S, R, k) interior values — ELL-format matrices only."""
        if not isinstance(self.interior, ELLBlock):
            raise AttributeError(
                f"data_loc is an ELL view; this DistMat stores its interior "
                f"as {self.fmt!r} (use mat.interior)"
            )
        return self.interior.data

    @property
    def col_loc(self) -> jax.Array:
        """(S, R, k) interior column ids — ELL-format matrices only."""
        if not isinstance(self.interior, ELLBlock):
            raise AttributeError(
                f"col_loc is an ELL view; this DistMat stores its interior "
                f"as {self.fmt!r} (use mat.interior)"
            )
        return self.interior.col

    # -- storage accounting ---------------------------------------------------

    @property
    def nnz_stored(self) -> int:
        """Stored value slots (incl. format padding) across all shards."""
        return self.interior.slots + _size(self.data_ext)

    def interior_stored_bytes(self, value_bytes: int = 8) -> int:
        """Interior bytes resident in HBM (values + indices, all shards)."""
        return self.interior.slots * value_bytes + self.interior.index_bytes

    def stored_bytes(self, value_bytes: int = 8) -> int:
        """Whole-matrix resident bytes: interior + boundary block + (deep
        halos only) the replicated ghost-row block."""
        return (
            self.interior_stored_bytes(value_bytes)
            + _size(self.data_ext) * (value_bytes + 4)
            + self.ghost_slots * (value_bytes + 4)
        )

    def spmv_flops(self) -> int:
        """2*nnz useful flops (upper bound incl. format padding slots)."""
        return 2 * self.nnz_stored


def DistELL(
    *,
    data_loc,
    col_loc,
    data_ext,
    col_ext,
    bnd_rows,
    send_sel,
    plan,
    n_global,
    row_starts,
    n_bnd=(),
) -> DistMat:
    """Back-compat constructor for the pre-refactor flat ELL layout: builds
    a :class:`DistMat` whose interior is an :class:`ELLBlock`."""
    return DistMat(
        interior=ELLBlock(data=data_loc, col=col_loc),
        data_ext=data_ext,
        col_ext=col_ext,
        bnd_rows=bnd_rows,
        send_sel=send_sel,
        plan=plan,
        n_global=n_global,
        row_starts=row_starts,
        n_bnd=n_bnd,
    )


# ---------------------------------------------------------------------------
# Builders
# ---------------------------------------------------------------------------


def _pad2(a: np.ndarray, rows: int, k: int, dtype) -> np.ndarray:
    out = np.zeros((rows, k), dtype=dtype)
    if a.size:
        out[: a.shape[0], : a.shape[1]] = a
    return out


def _rows_to_ell(rows_entries, n_rows: int, k: int, dtype):
    """rows_entries: list over rows of (cols int64 array, vals array)."""
    data = np.zeros((n_rows, k), dtype=dtype)
    col = np.zeros((n_rows, k), dtype=np.int32)
    for i, (c, v) in enumerate(rows_entries):
        m = len(c)
        if m:
            data[i, :m] = v
            col[i, :m] = c
    return data, col


# ---------------------------------------------------------------------------
# Interior packers: per-shard row lists -> one InteriorBlock
# ---------------------------------------------------------------------------


def _pack_interior_ell(shard_rows, R: int, dtype) -> ELLBlock:
    k = max(
        (len(c) for rows in shard_rows for c, _ in rows), default=0
    )
    k = max(k, 1)
    S = len(shard_rows)
    data = np.zeros((S, R, k), dtype)
    col = np.zeros((S, R, k), np.int32)
    for s, rows in enumerate(shard_rows):
        data[s], col[s] = _rows_to_ell(rows, R, k, dtype)
    return ELLBlock(data=_host(data), col=_host(col))


def _pack_interior_hyb(shard_rows, R: int, dtype, k_typ: int | None = None) -> HYBBlock:
    from repro.roofline.format_model import hyb_split

    lens = np.asarray(
        [len(c) for rows in shard_rows for c, _ in rows], np.int64
    )
    if k_typ is None:
        k_typ, _ = hyb_split(lens, n_rows=R * len(shard_rows))
    k_typ = max(int(k_typ), 1)
    S = len(shard_rows)
    tails = []
    for rows in shard_rows:
        td, tc, trw = [], [], []
        for r, (c, v) in enumerate(rows):
            if len(c) > k_typ:
                td.append(np.asarray(v[k_typ:], dtype))
                tc.append(np.asarray(c[k_typ:], np.int64))
                trw.append(np.full(len(c) - k_typ, r, np.int64))
        if td:
            tails.append(
                (np.concatenate(td), np.concatenate(tc), np.concatenate(trw))
            )
        else:
            tails.append(
                (np.zeros(0, dtype), np.zeros(0, np.int64), np.zeros(0, np.int64))
            )
    n_tail = tuple(len(t[0]) for t in tails)
    T = max(max(n_tail), 1)
    data = np.zeros((S, R, k_typ), dtype)
    col = np.zeros((S, R, k_typ), np.int32)
    tail_data = np.zeros((S, T), dtype)
    tail_col = np.zeros((S, T), np.int32)
    tail_row = np.zeros((S, T), np.int32)
    for s, rows in enumerate(shard_rows):
        prefix = [(c[:k_typ], v[:k_typ]) for c, v in rows]
        data[s], col[s] = _rows_to_ell(prefix, R, k_typ, dtype)
        td, tc, trw = tails[s]
        tail_data[s, : len(td)] = td
        tail_col[s, : len(td)] = tc.astype(np.int32)
        tail_row[s, : len(td)] = trw.astype(np.int32)
    return HYBBlock(
        data=_host(data),
        col=_host(col),
        tail_data=_host(tail_data),
        tail_col=_host(tail_col),
        tail_row=_host(tail_row),
        n_tail=n_tail,
    )


def _shard_rows_to_scipy(rows, R: int):
    import scipy.sparse as sp

    if rows:
        cols = np.concatenate([np.asarray(c, np.int64) for c, _ in rows])
        vals = np.concatenate([np.asarray(v, np.float64) for _, v in rows])
    else:
        cols, vals = np.zeros(0, np.int64), np.zeros(0)
    rids = np.repeat(
        np.arange(len(rows), dtype=np.int64), [len(c) for c, _ in rows]
    )
    return sp.coo_matrix((vals, (rids, cols)), shape=(R, R)).tocsr()


def _pack_interior_bcsr(shard_rows, R: int, dtype, br: int, bc: int) -> BCSRBlock:
    from repro.core.sparse import pack_bcsr

    packed = [
        pack_bcsr(_shard_rows_to_scipy(rows, R), br, bc, dtype)
        for rows in shard_rows
    ]
    n_brows = packed[0][2]
    bpr = max(p[3] for p in packed)
    S = len(shard_rows)
    blocks = np.zeros((S, n_brows * bpr, br, bc), dtype)
    bcol = np.zeros((S, n_brows * bpr), np.int32)
    for s, (bl, bcl, nbr, bpr_s, _) in enumerate(packed):
        # re-layout from the shard's own bpr_s to the fleet-wide bpr
        blocks[s].reshape(n_brows, bpr, br, bc)[:, :bpr_s] = bl.reshape(
            nbr, bpr_s, br, bc
        )
        bcol[s].reshape(n_brows, bpr)[:, :bpr_s] = bcl.reshape(nbr, bpr_s)
    return BCSRBlock(
        blocks=_host(blocks),
        bcol=_host(bcol),
        n_brows=n_brows,
        bpr=bpr,
        br=br,
        bc=bc,
    )


def pack_interior(
    fmt: str, shard_rows, R: int, *, dtype=np.float64, block=(4, 4)
) -> InteriorBlock:
    """Pack per-shard interior row lists into one :class:`InteriorBlock`.

    ``shard_rows``: per shard, a list over local rows of ``(cols, vals)``
    with locally-shifted int column ids. ``fmt`` is one of :data:`FORMATS`
    or ``"auto"``, which resolves the format minimizing the stored-bytes /
    traffic cost model (``roofline/format_model.choose_format``) — never
    costlier than ELL by construction, since ELL is always a candidate.
    """
    if fmt == "auto":
        from repro.roofline.format_model import choose_format

        fmt, _ = choose_format(
            [[len(c) for c, _ in rows] for rows in shard_rows],
            n_rows=R,
            shard_blocks=[
                _shard_block_stats(rows, R, block[0], block[1])
                for rows in shard_rows
            ],
            br=block[0],
            bc=block[1],
        )
    if fmt == "ell":
        return _pack_interior_ell(shard_rows, R, dtype)
    if fmt == "hyb":
        return _pack_interior_hyb(shard_rows, R, dtype)
    if fmt == "bcsr":
        return _pack_interior_bcsr(shard_rows, R, dtype, block[0], block[1])
    raise ValueError(f"unknown interior format {fmt!r}; want {FORMATS} or 'auto'")


def block_stats_from_arrays(
    r_loc: np.ndarray, c_loc: np.ndarray, R: int, br: int, bc: int
) -> tuple[int, int]:
    """(n_blocks, max_blocks_per_block_row) of one shard's interior, from
    flat local (row, col) index arrays.

    Single source of the BCSR block-counting formula — the packer/auto
    selector (via :func:`_shard_block_stats`) and the autotune pricing
    model (``autotune/prune.interior_stats``) must count the same tiles.
    """
    n_bcols = -(-R // bc)
    if not len(c_loc):
        return 0, 0
    keys = np.unique(
        (np.asarray(r_loc, np.int64) // br) * n_bcols
        + np.asarray(c_loc, np.int64) // bc
    )
    counts = np.bincount(keys // n_bcols)
    return len(keys), int(counts.max())


def _shard_block_stats(rows, R: int, br: int, bc: int) -> tuple[int, int]:
    """(n_blocks, max_blocks_per_block_row) of one shard's interior."""
    rids = np.repeat(
        np.arange(len(rows), dtype=np.int64), [len(c) for c, _ in rows]
    )
    cols = (
        np.concatenate([np.asarray(c, np.int64) for c, _ in rows])
        if rows
        else np.zeros(0, np.int64)
    )
    return block_stats_from_arrays(rids, cols, R, br, bc)


def _csr_rows_cols(indptr, indices, rows: np.ndarray) -> np.ndarray:
    """All column ids referenced by CSR ``rows`` (flat, duplicates kept)."""
    rows = np.asarray(rows, np.int64)
    starts = indptr[rows].astype(np.int64)
    lens = (indptr[rows + 1] - indptr[rows]).astype(np.int64)
    tot = int(lens.sum())
    if not tot:
        return np.zeros(0, np.int64)
    idx = np.repeat(starts - (np.cumsum(lens) - lens), lens) + np.arange(tot)
    return indices[idx]


def partition_csr(
    a_csr,
    n_shards: int,
    *,
    max_ring: int = 3,
    partition: RowPartition | None = None,
    dtype=np.float64,
    force_allgather: bool = False,
    fmt: str = "ell",
    block: tuple[int, int] = (4, 4),
    grid: tuple[int, int] | None = None,
    halo_depth: int = 1,
) -> DistMat:
    """Partition a host scipy CSR matrix into a DistMat.

    Chooses ring mode iff every off-shard coupling reaches at most
    ``max_ring`` shards away; otherwise falls back to allgather mode.
    ``force_allgather=True`` always uses allgather mode — this is the
    Ginkgo-analog baseline layout (full-vector gather, no halo
    minimization).

    ``fmt`` selects the interior storage format — one of :data:`FORMATS`
    (``ell``/``hyb``/``bcsr``) or ``"auto"`` (stored-bytes cost model, see
    ``roofline/format_model.py``); ``block`` is the BCSR tile shape. The
    boundary block and halo plan are format-agnostic.

    ``grid=(R, C)`` (with ``R * C == n_shards``) plans the halo exchange
    for a 2-D process grid instead: neighbor deltas become per-dimension
    ``(di, dj)`` shifts executed as chained sub-axis ppermutes
    (:class:`GridPlan`; ring-mode criterion applies per dimension). Rows
    remain contiguously block-partitioned over the flat shard order, so
    the vector layout — and, for ``grid=(1, N)``, the entire DistMat — is
    identical to the 1-D build. Pair with :func:`pencil_partition` to make
    the per-shard halo scale with the pencil surface.

    ``halo_depth=k`` builds k-deep ghost zones: the ghost-column set is the
    transitive closure of the boundary coupling to depth k (depth-(d+1)
    ghosts are the off-shard columns referenced by the depth-d ghost
    *rows*), so ONE widened exchange feeds k chained SpMV applications
    (``core/spmv.matrix_powers``). The matrix rows of the depth ``< k``
    ghosts are replicated into the ghost-row block for the redundant
    recompute. The ring criterion scales with depth (``max_ring * k``
    reach) — a matrix whose depth-1 coupling is ring-shaped stays ring at
    any depth. ``halo_depth=1`` is bit-identical to the historical build.
    """
    halo_depth = int(halo_depth)
    if halo_depth < 1:
        raise ValueError(f"halo_depth must be >= 1, got {halo_depth}")
    a = a_csr.tocsr()
    n = a.shape[0]
    part = partition or balanced_partition(n, n_shards)
    R = part.max_own

    if grid is not None:
        gr, gc = int(grid[0]), int(grid[1])
        if gr * gc != n_shards:
            raise ValueError(
                f"grid {gr}x{gc} does not cover n_shards={n_shards}"
            )
        if gr == 1:
            grid = None  # 1 x N *is* the 1-D layout; build it identically

    indptr, indices, vals = a.indptr, a.indices.astype(np.int64), a.data

    # --- pass 1: discover shifts + per-(shard,shift) needed columns --------
    # halo_depth > 1 widens the per-shard ghost set to the transitive
    # closure of the boundary coupling: depth-(d+1) ghosts are the
    # off-shard columns referenced by the depth-d ghost *rows*. All depths
    # merge into one sorted column set, so the existing recv/send planning
    # below widens without change (depth 1 reduces to the historical
    # np.unique of the boundary columns, bit for bit).
    owners_cache = {}
    depth_cache = {}  # s -> per-ghost-column depth, aligned with ext_cols
    shifts_seen: set = set()  # int deltas (1-D) or (di, dj) tuples (grid)
    for s in range(n_shards):
        lo, hi = part.owner_range(s)
        cols = indices[indptr[lo] : indptr[hi]]
        own_mask = (cols >= lo) & (cols < hi)
        frontier = np.unique(cols[~own_mask])
        ghost_cols = [frontier]
        ghost_depths = [np.full(len(frontier), 1, np.int64)]
        for depth in range(2, halo_depth + 1):
            if not len(frontier):
                break
            ref = np.unique(_csr_rows_cols(indptr, indices, frontier))
            ref = ref[(ref < lo) | (ref >= hi)]  # off-shard columns only
            frontier = np.setdiff1d(
                ref, np.concatenate(ghost_cols), assume_unique=True
            )
            ghost_cols.append(frontier)
            ghost_depths.append(np.full(len(frontier), depth, np.int64))
        merged = np.concatenate(ghost_cols)
        order = np.argsort(merged)
        ext_cols = merged[order]
        owners = part.owner_of(ext_cols)
        owners_cache[s] = (ext_cols, owners)
        depth_cache[s] = np.concatenate(ghost_depths)[order]
        if grid is not None:
            di = owners // gc - s // gc
            dj = owners % gc - s % gc
            shifts_seen.update(zip(di.tolist(), dj.tolist()))
        else:
            for d in np.unique(owners - s):
                shifts_seen.add(int(d))

    reach = max_ring * halo_depth
    if grid is not None:
        near = all(max(abs(di), abs(dj)) <= reach for di, dj in shifts_seen)
        mode = "grid" if near else "allgather"
    else:
        mode = (
            "ring" if all(abs(d) <= reach for d in shifts_seen) else "allgather"
        )
    if force_allgather:
        mode = "allgather"
    if grid is not None:
        shifts = tuple(
            sorted(shifts_seen, key=lambda t: (max(abs(t[0]), abs(t[1])), t))
        )
    else:
        shifts = tuple(sorted(shifts_seen, key=lambda d: (abs(d), d)))

    if mode == "ring":
        # recv_lists[k][i]: sorted global cols shard i receives from i+shifts[k]
        recv_lists = [[np.zeros(0, np.int64) for _ in range(n_shards)] for _ in shifts]
        for s in range(n_shards):
            ext_cols, owners = owners_cache[s]
            for k, d in enumerate(shifts):
                sel = owners == s + d
                recv_lists[k][s] = ext_cols[sel]
        widths = tuple(
            max((len(recv_lists[k][i]) for i in range(n_shards)), default=0)
            for k in range(len(shifts))
        )
        plan = HaloPlan("ring", shifts, widths, R, n_shards)

        # send_sel[j]: for shift k, shard j sends x_own[sel] to j - shifts[k];
        # the receiver (j - d) needs recv_lists[k][j - d] (cols owned by j).
        W = sum(widths)
        send_sel = np.zeros((n_shards, max(W, 1)), np.int32)
        for j in range(n_shards):
            off = 0
            jlo, _ = part.owner_range(j)
            for k, d in enumerate(shifts):
                i = j - d  # receiver
                if 0 <= i < n_shards:
                    g = recv_lists[k][i]
                    send_sel[j, off : off + len(g)] = (g - jlo).astype(np.int32)
                off += widths[k]
    elif mode == "grid":
        # Same recv-list construction, with (di, dj) grid deltas: shard
        # (i, j) receives recv_lists[k][s] from shard (i+di, j+dj).
        recv_lists = [[np.zeros(0, np.int64) for _ in range(n_shards)] for _ in shifts]
        for s in range(n_shards):
            ext_cols, owners = owners_cache[s]
            di = owners // gc - s // gc
            dj = owners % gc - s % gc
            for k, (ki, kj) in enumerate(shifts):
                sel = (di == ki) & (dj == kj)
                recv_lists[k][s] = ext_cols[sel]
        widths = tuple(
            max((len(recv_lists[k][i]) for i in range(n_shards)), default=0)
            for k in range(len(shifts))
        )
        plan = GridPlan("grid", (gr, gc), shifts, widths, R, n_shards)

        # Sender (ji, jj) serves the receiver at (ji - di, jj - dj); the
        # chained per-dimension ppermutes deliver the buffer unchanged, so
        # the sender packs it in the receiver's recv-list order.
        W = sum(widths)
        send_sel = np.zeros((n_shards, max(W, 1)), np.int32)
        for j in range(n_shards):
            off = 0
            jlo, _ = part.owner_range(j)
            ji, jj = divmod(j, gc)
            for k, (ki, kj) in enumerate(shifts):
                ri, rj = ji - ki, jj - kj  # receiver grid position
                if 0 <= ri < gr and 0 <= rj < gc:
                    g = recv_lists[k][ri * gc + rj]
                    send_sel[j, off : off + len(g)] = (g - jlo).astype(np.int32)
                off += widths[k]
    else:
        plan = HaloPlan("allgather", (), (), R, n_shards)
        send_sel = np.zeros((n_shards, 1), np.int32)
        recv_lists = None

    # --- pass 2: build the split interior/boundary blocks -------------------
    k_ext_max = 1
    per_shard = []
    ghost_lists = []  # per shard: (x_ext col ids, vals, own x_ext pos) rows
    for s in range(n_shards):
        lo, hi = part.owner_range(s)
        loc_rows, ext_rows = [], []
        # Map global ext col -> x_ext position for this shard.
        if mode != "allgather":
            ext_map = {}
            for k in range(len(shifts)):
                base = plan.buf_offset(k)
                for p, g in enumerate(recv_lists[k][s]):
                    ext_map[int(g)] = base + p
        # Ghost-row block: replicate the rows of the depth < halo_depth
        # ghosts, with columns remapped into this shard's x_ext space (own
        # columns land in [0, n_own), closure guarantees every off-shard
        # column is in ext_map).
        ghost_rows_s = []
        if halo_depth > 1 and mode != "allgather":
            deep = owners_cache[s][0][depth_cache[s] < halo_depth]
            for g in deep:
                g = int(g)
                gcols = indices[indptr[g] : indptr[g + 1]]
                gvals = vals[indptr[g] : indptr[g + 1]]
                lidx = np.fromiter(
                    (
                        int(c) - lo if lo <= c < hi else ext_map[int(c)]
                        for c in gcols
                    ),
                    dtype=np.int64,
                    count=len(gcols),
                )
                ghost_rows_s.append((lidx, gvals, ext_map[g]))
        ghost_lists.append(ghost_rows_s)
        for r in range(lo, hi):
            cs = indices[indptr[r] : indptr[r + 1]]
            vs = vals[indptr[r] : indptr[r + 1]]
            own = (cs >= lo) & (cs < hi)
            loc_rows.append(((cs[own] - lo).astype(np.int64), vs[own]))
            ec, ev = cs[~own], vs[~own]
            if mode != "allgather":
                lidx = np.fromiter(
                    (ext_map[int(g)] for g in ec), dtype=np.int64, count=len(ec)
                )
            else:
                # padded global layout: owner * R + (g - owner_start)
                owners = part.owner_of(ec)
                starts = np.asarray(part.row_starts, np.int64)[owners]
                lidx = owners * R + (ec - starts)
            ext_rows.append((lidx, ev))
            k_ext_max = max(k_ext_max, len(ec))
        per_shard.append((loc_rows, ext_rows))

    S = n_shards
    interior = pack_interior(
        fmt, [loc_rows for loc_rows, _ in per_shard], R, dtype=dtype,
        block=block,
    )
    # Interior/boundary row split: boundary rows are the rows with at least
    # one external (ghost-column) entry; only they get boundary-block slots.
    bnd_lists = [
        [r for r, (_, ev) in enumerate(ext_rows) if len(ev)]
        for _, ext_rows in per_shard
    ]
    n_bnd = tuple(len(b) for b in bnd_lists)
    B = max(max(n_bnd), 1)
    data_ext = np.zeros((S, B, k_ext_max), dtype)
    col_ext = np.zeros((S, B, k_ext_max), np.int32)
    bnd_rows = np.zeros((S, B), np.int32)
    for s, (_, ext_rows) in enumerate(per_shard):
        bnd = bnd_lists[s]
        de, ce = _rows_to_ell([ext_rows[r] for r in bnd], B, k_ext_max, dtype)
        data_ext[s], col_ext[s] = de, ce
        bnd_rows[s, : len(bnd)] = bnd

    # Pack the ghost-row block (0-sized at depth 1 / allgather). Padding
    # rows scatter to position ext_len — out of range, dropped on device.
    eff_depth = halo_depth if mode != "allgather" else 1
    G = max((len(gr) for gr in ghost_lists), default=0)
    kg = max((len(c) for gr in ghost_lists for c, _, _ in gr), default=0)
    kg = max(kg, 1) if G else 1
    ghost_data = np.zeros((S, G, kg), dtype)
    ghost_col = np.zeros((S, G, kg), np.int32)
    ghost_pos = np.full((S, G), plan.ext_len, np.int32)
    for s, gr in enumerate(ghost_lists):
        for j, (c, v, pos) in enumerate(gr):
            m = len(c)
            ghost_data[s, j, :m] = v
            ghost_col[s, j, :m] = c.astype(np.int32)
            ghost_pos[s, j] = pos

    return DistMat(
        interior=interior,
        data_ext=_host(data_ext),
        col_ext=_host(col_ext),
        bnd_rows=_host(bnd_rows),
        send_sel=_host(send_sel),
        plan=plan,
        n_global=n,
        row_starts=part.row_starts,
        n_bnd=n_bnd,
        ghost_data=_host(ghost_data),
        ghost_col=_host(ghost_col),
        ghost_pos=_host(ghost_pos),
        halo_depth=eff_depth,
    )


def partition_stencil(
    p, n_shards: int, dtype=np.float64, mode: str = "ring",
    fmt: str = "ell", block: tuple[int, int] = (4, 4),
) -> DistMat:
    """Build a DistMat for a Poisson stencil problem WITHOUT materializing the
    global matrix: per-shard cost is O(n_local * k).

    Slab (z-plane) partition; both stencils reach exactly +-1 plane, so the
    halo plan is always ring mode with shifts (-1, +1) and width = nx*ny
    (except at single-shard, where there is no exchange).

    ``mode="allgather"`` builds the Ginkgo-analog layout instead (external
    columns in padded-global layout; full-vector gather at SpMV time).
    ``fmt`` selects the interior format as in :func:`partition_csr`; stencil
    rows are uniform-width, so ``"auto"`` resolves to ELL and the other
    formats exist for A/B measurements only.
    """
    from repro.matrices.poisson import stencil_offsets, stencil_values

    part = plane_partition(p.n, p.plane, n_shards)
    R = part.max_own
    H = p.plane
    offs = stencil_offsets(p.stencil)
    k = len(offs)
    svals = stencil_values(p)
    # Entries per row reaching planes z-1 / z / z+1.
    off_dz = offs[:, 2]
    k_ext = max(int((off_dz == -1).sum()), int((off_dz == 1).sum()))

    if n_shards > 1 and mode == "ring":
        shifts, widths = (-1, 1), (H, H)
    else:
        shifts, widths = (), ()
    plan = HaloPlan(mode if n_shards > 1 else "ring", shifts, widths, R, n_shards)

    S = n_shards
    data_loc = np.zeros((S, R, k), dtype)
    col_loc = np.zeros((S, R, k), np.int32)
    # Boundary rows live in the slab's first/last z-plane only: at most 2H
    # ghost-touching rows per shard (H for the edge shards / S == 2).
    B_ub = min(2 * H, R) if S > 1 else 1
    data_ext = np.zeros((S, B_ub, max(k_ext, 1)), dtype)
    col_ext = np.zeros((S, B_ub, max(k_ext, 1)), np.int32)
    bnd_rows = np.zeros((S, B_ub), np.int32)
    n_bnd = [0] * S
    W = sum(widths)
    send_sel = np.zeros((S, max(W, 1)), np.int32)

    for s in range(S):
        lo, hi = part.owner_range(s)
        z0, z1 = lo // H, hi // H
        n_own = hi - lo
        zz, yy, xx = np.meshgrid(
            np.arange(z0, z1), np.arange(p.ny), np.arange(p.nx), indexing="ij"
        )
        coords = np.stack([xx.ravel(), yy.ravel(), zz.ravel()], axis=1)
        nbr = coords[:, None, :] + offs[None, :, :]  # (n_own, k, 3)
        valid = (
            (nbr[..., 0] >= 0)
            & (nbr[..., 0] < p.nx)
            & (nbr[..., 1] >= 0)
            & (nbr[..., 1] < p.ny)
            & (nbr[..., 2] >= 0)
            & (nbr[..., 2] < p.nz)
        )
        gcol = nbr[..., 0] + p.nx * (nbr[..., 1] + p.ny * nbr[..., 2])
        vals = np.broadcast_to(svals[None, :], valid.shape) * valid

        own = valid & (gcol >= lo) & (gcol < hi)
        ext = valid & ~own
        # local part
        dl = np.where(own, vals, 0.0).astype(dtype)
        cl = np.where(own, gcol - lo, 0).astype(np.int32)
        data_loc[s, :n_own], col_loc[s, :n_own] = dl, cl
        # ext part: left plane (z0-1) -> buffer 0; right plane (z1) -> buffer 1
        if S > 1:
            left = ext & (gcol < lo)
            right = ext & (gcol >= hi)
            # position within plane = gcol mod H
            pos = (gcol % H).astype(np.int64)
            if mode == "ring":
                lcol = np.where(left, R + pos, 0) + np.where(right, R + H + pos, 0)
            else:
                gsafe = np.where(ext, gcol, lo)
                owners = part.owner_of(gsafe.ravel()).reshape(gsafe.shape)
                starts = np.asarray(part.row_starts, np.int64)[owners]
                lcol = np.where(ext, owners * R + (gsafe - starts), 0)
            de = np.where(ext, vals, 0.0).astype(dtype)
            # compact ext entries into k_ext slots per row
            order = np.argsort(~ext, axis=1, kind="stable")  # ext first
            de_s = np.take_along_axis(de, order, axis=1)[:, :k_ext]
            ce_s = np.take_along_axis(
                np.where(ext, lcol, 0).astype(np.int32), order, axis=1
            )[:, :k_ext]
            # ...and compact the ghost-touching rows into the boundary block
            bnd = np.nonzero(ext.any(axis=1))[0]
            n_bnd[s] = len(bnd)
            data_ext[s, : len(bnd)] = de_s[bnd]
            col_ext[s, : len(bnd)] = ce_s[bnd]
            bnd_rows[s, : len(bnd)] = bnd.astype(np.int32)
            # send selectors: shift -1 (recv from left): shard j sends its LAST
            # plane to j+1 <=> under perm (j, j-(-1))... define per plan.perm:
            # shift d=-1: receiver i gets from i-1; sender j sends to j+1 its
            # last plane rows [n_own-H, n_own).
            # shift d=+1: sender j sends to j-1 its first plane rows [0, H).
            off = 0
            for kk, d in enumerate(shifts):
                if d == -1:
                    sel = np.arange(n_own - H, n_own, dtype=np.int32)
                else:
                    sel = np.arange(0, H, dtype=np.int32)
                send_sel[s, off : off + H] = sel
                off += widths[kk]

    B = max(max(n_bnd), 1)
    if fmt in ("ell", "auto"):
        interior = ELLBlock(data=_host(data_loc), col=_host(col_loc))
    else:
        interior = pack_interior(
            fmt, _ell_to_shard_rows(data_loc, col_loc), R, dtype=dtype,
            block=block,
        )
    return DistMat(
        interior=interior,
        data_ext=_host(data_ext[:, :B]),
        col_ext=_host(col_ext[:, :B]),
        bnd_rows=_host(bnd_rows[:, :B]),
        send_sel=_host(send_sel),
        plan=plan,
        n_global=p.n,
        row_starts=part.row_starts,
        n_bnd=tuple(n_bnd),
    )


def _ell_to_shard_rows(data: np.ndarray, col: np.ndarray):
    """Recover per-shard (cols, vals) row lists from packed ELL arrays.

    Entries are identified by ``data != 0 or col != 0`` — the repo-wide
    padding convention; a genuine zero-valued entry at column 0 (which no
    stencil produces) would be dropped, hence this is only used to convert
    stencil-built interiors to the alternative formats.
    """
    S, R, _ = data.shape
    out = []
    for s in range(S):
        rows = []
        for r in range(R):
            m = (data[s, r] != 0) | (col[s, r] != 0)
            rows.append((col[s, r][m].astype(np.int64), data[s, r][m]))
        out.append(rows)
    return out


def expand_boundary(mat: DistMat) -> tuple[np.ndarray, np.ndarray]:
    """Full-row ``(S, R, k_ext)`` view of the compact boundary block (host).

    Inverse of the boundary-row compaction: scatter each shard's compact
    ``(B, k_ext)`` ghost-entry rows back to their ``bnd_rows`` positions.
    Tests use this to rebuild the pre-split ("unsplit") SpMV formulation and
    check the interior/boundary split reproduces it bitwise.
    """
    S, R = mat.n_shards, mat.n_own_pad
    de = np.asarray(mat.data_ext)
    ce = np.asarray(mat.col_ext)
    rows = np.asarray(mat.bnd_rows)
    k = de.shape[-1]
    full_d = np.zeros((S, R, k), de.dtype)
    full_c = np.zeros((S, R, k), ce.dtype)
    for s in range(S):
        nb = mat.n_bnd[s] if mat.n_bnd else 0
        full_d[s, rows[s, :nb]] = de[s, :nb]
        full_c[s, rows[s, :nb]] = ce[s, :nb]
    return full_d, full_c


# ---------------------------------------------------------------------------
# Distributed vectors (host <-> device layout helpers)
# ---------------------------------------------------------------------------


def pad_vector(x: np.ndarray, mat: DistMat) -> np.ndarray:
    """Global vector -> (S, R) padded shard layout."""
    with jax.profiler.TraceAnnotation("repro.pad_vector"):
        S, R = mat.n_shards, mat.n_own_pad
        out = np.zeros((S, R), x.dtype)
        for s in range(S):
            lo, hi = mat.row_starts[s], mat.row_starts[s + 1]
            out[s, : hi - lo] = x[lo:hi]
        return out


def unpad_vector(xp: np.ndarray, mat: DistMat) -> np.ndarray:
    """(S, R) padded shard layout -> global vector. A device ``xp`` is
    copied to the host inside the ``repro.unpad_vector`` span."""
    with jax.profiler.TraceAnnotation("repro.unpad_vector"):
        xp = np.asarray(xp)
        parts = []
        for s in range(mat.n_shards):
            lo, hi = mat.row_starts[s], mat.row_starts[s + 1]
            parts.append(xp[s, : hi - lo])
        return np.concatenate(parts)


def pad_block(X: np.ndarray, mat: DistMat) -> np.ndarray:
    """Global (n, r) RHS block -> (S, R, r) padded shard layout."""
    S, R = mat.n_shards, mat.n_own_pad
    out = np.zeros((S, R, X.shape[1]), X.dtype)
    for s in range(S):
        lo, hi = mat.row_starts[s], mat.row_starts[s + 1]
        out[s, : hi - lo] = X[lo:hi]
    return out


def unpad_block(Xp: np.ndarray, mat: DistMat) -> np.ndarray:
    """(S, R, r) padded shard layout -> global (n, r) block."""
    Xp = np.asarray(Xp)
    parts = []
    for s in range(mat.n_shards):
        lo, hi = mat.row_starts[s], mat.row_starts[s + 1]
        parts.append(Xp[s, : hi - lo])
    return np.concatenate(parts)
