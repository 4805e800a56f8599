"""Unified pluggable consensus layer — every ``Z ← W Z`` in one place.

The AGREE protocol is the communication heart of the AltGDmin family,
but before this module each execution surface re-derived the mixing
product independently: the simulator's stacked scan (core/agree.py), the
mesh runtime's inline ppermute chain (core/runtime.py), the trainer's
roll form (distributed/gossip.py / aggregation.py), and the engine's
fused ``W^{T_con}`` combine (core/engine.py).  A :class:`CombineRule`
now owns all of them, with three lowered forms per rule:

  * **simulator** — stacked node axis, ``Z: (L, ...)``.  The unfused
    lowering is the exact sequential product (dtype-preserving, the
    numerics anchor); fused backends hoist onto a precomputed dense
    mixer executed by ``kernels/gossip_axpy.mix_rows`` (one weighted
    combine instead of T_con HBM sweeps).
  * **mesh** — one node per device inside ``shard_map``.  Each gossip
    round exchanges blocks by ``lax.ppermute`` and then combines them:
    the unfused lowering is the sequential weighted-sum chain, the fused
    lowering is ONE (K+1)-way ``kernels/gossip_axpy.gossip_combine``
    dispatch per round.  Any weighted graph lowers this way
    (:func:`mesh_weights_from_matrix`): one permute per distinct cyclic
    shift of W's sparsity pattern, each device combining with its own W
    row — circulant matrices collapse to shared scalar weights.
  * **comm signature** — a :class:`CommSignature` consumed by
    :mod:`repro.core.comm_model` and the API's wall-clock pricing, so a
    rule's communication cost is declared next to its math.

Precision policy (shared by every lowering): the fused combine kernels
accumulate in f32, so float64 operands always take the exact unfused
path — x64 simulations are never silently truncated in the consensus
phase.  Lower-precision operands (bf16 wire dtypes) accumulate in the
promoted f32 dtype on the unfused path too, matching the kernels.

Rules registered here: ``gossip`` (the paper's T_con-round AGREE),
``neighbor`` (DGD's single self-excluding exchange), ``central`` (fusion
center), ``none`` (no communication), plus the related-work combines —
``exact_diffusion`` (the projection-corrected combine of *Exact Subspace
Diffusion for Decentralized Multitask Learning*, arXiv:2304.07358) and
``beyond_central`` (the communication-efficient single-round combine of
*Beyond Centralization*, arXiv:2512.22675) — and the compressed wire
rules ``topk_gossip`` / ``quantized_gossip`` / ``event_gossip`` (see
:class:`CompressedGossipCombine`: stateful encode, compact payloads,
error feedback).  The dropout-tolerant rules ``partial_gossip`` /
``stale_gossip`` / ``push_sum_gossip`` (see
:class:`MaskedGossipCombine`) take a per-iteration availability mask:
masked weight renormalization, last-delivered stale copies, and
bias-corrected push-sum weight carry respectively — with availability
≡ 1 the first two reproduce dense gossip bit-for-bit.
``register_rule`` is open.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class CommSignature:
    """What a combine rule costs on the wire, per outer iteration.

    ``pattern`` prices the exchange shape: ``"gossip"`` /``"neighbor"``
    send the iterate to every graph neighbour ``rounds_per_iter`` times;
    ``"central"`` is one gather + one broadcast; ``"none"`` is silent.

    ``entries_per_round`` / ``bytes_per_entry`` describe the PAYLOAD of
    one message: ``None`` means the dense d×r iterate at the network
    model's native precision (every uncompressed rule), while the
    compressed rules fill both so the pricing layer
    (:func:`repro.core.comm_model.time_axis_from_signature`) sees the
    smaller wire format instead of silently assuming a dense exchange.
    """
    pattern: str                 # "gossip" | "neighbor" | "central" | "none"
    rounds_per_iter: int
    entries_per_round: Optional[int] = None   # None → dense d·r
    bytes_per_entry: Optional[int] = None     # None → the model's native

    def bytes_per_iter(self, n_entries: int, itemsize: int, n_nodes: int,
                       degree: int) -> int:
        """Bytes sent per node per outer iteration (benchmark tables).
        The signature's own payload fields override the dense
        ``n_entries`` / ``itemsize`` arguments when set."""
        n = (self.entries_per_round if self.entries_per_round is not None
             else n_entries)
        bpe = (self.bytes_per_entry if self.bytes_per_entry is not None
               else itemsize)
        if self.pattern == "central":
            # ring all-reduce equivalent: 2·(L−1)/L · size
            return int(2 * (n_nodes - 1) / n_nodes * n * bpe)
        return int(self.rounds_per_iter * degree * n * bpe)

    def network_bytes_per_iter(self, n_entries: int, itemsize: int, *,
                               n_nodes: int, n_edges: int) -> int:
        """TOTAL bytes the whole network moves per outer iteration,
        derived from the graph's edge set (degree-weighted: one message
        per directed edge per round, Σ_g deg_g = 2·|E|) — NOT from an
        L² all-pairs assumption.  Dense and sparse representations of
        the same graph report the same ``n_edges``, so they price
        identically (the consistency regression); the scale benchmark
        reports this next to per-node :meth:`bytes_per_iter`."""
        n = (self.entries_per_round if self.entries_per_round is not None
             else n_entries)
        bpe = (self.bytes_per_entry if self.bytes_per_entry is not None
               else itemsize)
        if self.pattern == "none" or self.rounds_per_iter == 0:
            return 0
        if self.pattern == "central":
            # L uploads + L downloads of the iterate
            return int(2 * n_nodes * n * bpe)
        return int(self.rounds_per_iter * 2 * n_edges * n * bpe)


# ----------------------------------------------------------------------
# the combine primitives every lowering bottoms out in
# ----------------------------------------------------------------------

def _acc_dtype(dtype):
    return jnp.promote_types(dtype, jnp.float32)


def _fused_wanted(backend: str, dtype) -> bool:
    """Fused Pallas combines accumulate in f32: take them only on the
    pallas backends and never for float64 operands (x64 policy)."""
    return backend != "xla-ref" and jnp.dtype(dtype) != jnp.float64


def combine_blocks(z, neighbors: Sequence[jax.Array], weights, *,
                   backend: str = "xla-ref"):
    """ONE (K+1)-way weighted combine ``z ← w₀·z + Σ_k w_{k+1}·nbr_k`` —
    the primitive under every mesh lowering (ppermute rounds, trainer
    roll rounds).  ``weights`` is a length-K+1 sequence: Python floats
    for uniform circulant weights, or a (K+1,) array slice of the
    device's own W row for arbitrary weighted topologies.  Unfused: the
    sequential chain in the promoted accumulator dtype; fused: a single
    ``gossip_combine`` dispatch."""
    from repro.kernels import ops
    neighbors = list(neighbors)
    if neighbors and _fused_wanted(backend, z.dtype):
        return ops.gossip_combine(z, jnp.stack(neighbors), weights,
                                  backend=backend)
    acc_dt = _acc_dtype(z.dtype)
    w = (list(weights) if isinstance(weights, (tuple, list))
         else list(jnp.asarray(weights).astype(acc_dt)))
    acc = w[0] * z.astype(acc_dt)
    for k, nbr in enumerate(neighbors):
        acc = acc + w[k + 1] * nbr.astype(acc_dt)
    return acc.astype(z.dtype)


def stacked_product(Z: jax.Array, W, T_con: int) -> jax.Array:
    """The exact sequential simulator product: T_con rounds of ``W @ Z``
    over the leading node axis, dtype-preserving (the seed's ``agree``
    math — every other lowering is validated against this).  ``W`` may
    be a :class:`~repro.distributed.mixing.SparseWeights`, in which case
    each round is the padded-COO segment-sum of
    :func:`stacked_sparse_product` instead of a dense matmul."""
    from repro.distributed.mixing import SparseWeights
    if isinstance(W, SparseWeights):
        return stacked_sparse_product(Z, W, T_con)
    if T_con == 0:
        return Z
    W = W.astype(Z.dtype)
    flat = Z.reshape(Z.shape[0], -1)

    def body(carry, _):
        return W @ carry, None

    out, _ = jax.lax.scan(body, flat, None, length=T_con)
    return out.reshape(Z.shape)


def stacked_dense_mix(Z: jax.Array, M, *, backend: str):
    """Single combine ``Z ← M Z`` for a precomputed mixer (e.g.
    ``W^{T_con}``): fused ``mix_rows`` on the pallas backends, einsum on
    xla-ref/f64.  A :class:`SparseWeights` mixer takes the segment-sum
    lowering instead (one sparse round, any backend)."""
    from repro.distributed.mixing import SparseWeights
    from repro.kernels import ops
    if isinstance(M, SparseWeights):
        return stacked_sparse_product(Z, M, 1)
    if _fused_wanted(backend, Z.dtype):
        return ops.mix_nodes(Z, M.astype(jnp.float32),
                             backend=backend).astype(Z.dtype)
    return jnp.einsum("gh,h...->g...", M.astype(Z.dtype), Z)


# ----------------------------------------------------------------------
# sparse simulator lowering
# ----------------------------------------------------------------------
#
# Above a node-count/density cutoff the (L, L) mixing matrix is pure
# overhead: every combine rule can lower to "gather sender rows by
# col_idx, weight, segment-sum into receivers" on the padded edge list a
# SparseWeights carries.  The edge arrays are padded to a multiple of
# _SPARSE_PAD entries so nearby sizes share compiled executables; the
# padding entries point at dummy segment L with weight exactly 0.0, so
# they are arithmetically invisible (the padding-neutrality test pins
# this).  Edges are CSR-sorted by receiver row with the padding at the
# end, so ``segment_sum(..., indices_are_sorted=True)`` is valid.

SPARSE_MIN_NODES = 512
SPARSE_DENSITY_THRESHOLD = 0.25
_SPARSE_PAD = 1024


def maybe_sparsify(W):
    """Auto-select the sparse simulator lowering for a concrete dense
    mixing matrix: above :data:`SPARSE_MIN_NODES` nodes AND at or below
    :data:`SPARSE_DENSITY_THRESHOLD` off-diagonal density, return the
    equivalent :class:`~repro.distributed.mixing.SparseWeights`;
    otherwise (small L, dense graph, traced operand, or anything that
    is not a square matrix) return ``W`` unchanged.  An explicit
    ``SparseWeights`` input passes straight through — a caller that
    built one has already chosen the representation."""
    from repro.distributed.mixing import SparseWeights
    if isinstance(W, SparseWeights) or W is None:
        return W
    if isinstance(W, jax.core.Tracer):
        return W
    try:
        Wn = np.asarray(W)
    except Exception:
        return W
    if Wn.ndim != 2 or Wn.shape[0] != Wn.shape[1]:
        return W
    L = Wn.shape[0]
    if L < SPARSE_MIN_NODES or L < 2:
        return W
    off = np.count_nonzero(Wn) - np.count_nonzero(np.diag(Wn))
    if off / (L * (L - 1)) > SPARSE_DENSITY_THRESHOLD:
        return W
    return SparseWeights.from_dense(Wn)


def _padded_coo(rows, cols, vals, n: int):
    """Pad host COO arrays to a multiple of :data:`_SPARSE_PAD` entries:
    padding rows point at dummy segment ``n``, padding cols at 0, and
    padding weights are exactly 0.0."""
    nnz = int(vals.size)
    total = max(_SPARSE_PAD,
                -(-nnz // _SPARSE_PAD) * _SPARSE_PAD)
    pad = total - nnz
    return (np.concatenate([rows, np.full(pad, n, np.int32)]),
            np.concatenate([cols, np.zeros(pad, np.int32)]),
            np.concatenate([vals, np.zeros(pad)]))


def _sparse_arrays(sw):
    """(rows, cols, vals, diag) padded host arrays of a SparseWeights —
    the static operands every sparse mixer closes over."""
    rows, cols, vals = _padded_coo(sw.rows, sw.cols, sw.vals, sw.n)
    return rows, cols, vals, sw.diag


def sparse_round(Zf, rows, cols, vals, diag, L: int):
    """ONE ``Z ← W Z`` on the padded edge list, ``Zf: (L, F)``: gather
    sender rows by ``cols``, weight, ``segment_sum`` into receiver rows
    (dummy segment L absorbs the padding), then add the separate
    diagonal term.  ``vals``/``diag`` must already be in ``Zf.dtype``
    (the caller casts once, mirroring ``stacked_product``'s
    ``W.astype``)."""
    gathered = vals[:, None] * Zf[cols]
    acc = jax.ops.segment_sum(gathered, rows, num_segments=L + 1,
                              indices_are_sorted=True)
    return acc[:L] + diag[:, None] * Zf


def sparse_offdiag_apply(Zf, rows, cols, vals, L: int):
    """The off-diagonal half of :func:`sparse_round` — ``(W − diag) Z``
    — for combines that treat the self term specially (the compressed
    rules' exact-self correction)."""
    gathered = vals[:, None] * Zf[cols]
    acc = jax.ops.segment_sum(gathered, rows, num_segments=L + 1,
                              indices_are_sorted=True)
    return acc[:L]


def stacked_sparse_product(Z: jax.Array, sw, T_con: int) -> jax.Array:
    """T_con sequential rounds of the sparse ``Z ← W Z`` — the sparse
    twin of :func:`stacked_product`, dtype-preserving (weights cast to
    ``Z.dtype`` exactly like the dense path's ``W.astype``)."""
    if T_con == 0:
        return Z
    L = sw.n
    rows, cols, vals, diag = _sparse_arrays(sw)
    rows, cols = jnp.asarray(rows), jnp.asarray(cols)
    vals = jnp.asarray(vals, Z.dtype)
    diag = jnp.asarray(diag, Z.dtype)
    flat = Z.reshape(L, -1)

    def body(carry, _):
        return sparse_round(carry, rows, cols, vals, diag, L), None

    out, _ = jax.lax.scan(body, flat, None, length=T_con)
    return out.reshape(Z.shape)


def node_mean(Z: jax.Array) -> jax.Array:
    """Fusion-center combine: exact mean over the node axis, broadcast
    back (lowers to one all-reduce under pjit)."""
    acc_dt = _acc_dtype(Z.dtype)
    m = jnp.mean(Z.astype(acc_dt), axis=0, keepdims=True)
    return jnp.broadcast_to(m, Z.shape).astype(Z.dtype)


def neighbor_average_matrix(adj):
    """DGD's row-stochastic neighbour average M = D⁻¹A (zero diagonal,
    isolated nodes guarded to degree 1).  ONE derivation shared by the
    simulator driver and the mesh lowering — their ≤1e-7 parity depends
    on both sides using the same matrix.  A
    :class:`~repro.distributed.graphs.SparseGraph` adjacency yields the
    equivalent :class:`SparseWeights` (same per-edge 1/deg values,
    never densified)."""
    from repro.distributed.graphs import Graph, SparseGraph
    from repro.distributed.mixing import neighbor_average_weights_sparse
    if isinstance(adj, SparseGraph):
        return neighbor_average_weights_sparse(adj)
    if isinstance(adj, Graph):
        adj = jnp.asarray(adj.adj, jnp.float64)  # reprolint: allow=RL002 — dense-Graph input tier; SparseGraph returns sparse above
    deg = jnp.maximum(jnp.sum(adj, axis=1), 1.0)
    return adj / deg[:, None]


def mesh_weights_from_matrix(W) -> tuple[tuple[int, ...], np.ndarray]:
    """Decompose a concrete (L, L) mixing matrix into cyclic-shift form:
    ``(shifts, table)`` with ``table[i] = [W_ii, W_{i,(i+s1)%L}, ...]``.

    Every entry of W lies on exactly one cyclic diagonal (edge (i, j) on
    shift ``(j−i) mod L``), so ANY weighted graph lowers to one
    ``lax.ppermute`` per distinct shift plus one (K+1)-way weighted
    combine — a circulant matrix needs exactly its own |shifts|, an
    irregular graph up to L−1.  Shifts are reported as signed
    representatives in (−L/2, L/2] and sorted, so a symmetric ring
    decomposes to the runtime's historical (−1, 1) order.

    W must be host-concrete (topology is static metadata, never traced).
    A :class:`SparseWeights` densifies first (the per-device mesh tier
    is small-L by construction; the large-L mesh form is
    :class:`VirtualTopology`).
    """
    from repro.distributed.mixing import SparseWeights
    if isinstance(W, SparseWeights):
        W = W.to_dense()  # reprolint: allow=RL002 — per-device mesh tier is small-L by construction; large-L uses VirtualTopology
    try:
        Wn = np.asarray(W)
    except Exception as e:                       # jax TracerConversionError
        raise ValueError(
            "mesh_weights_from_matrix needs a concrete mixing matrix — "
            "topology is static metadata and cannot be traced") from e
    if Wn.ndim != 2 or Wn.shape[0] != Wn.shape[1]:
        raise ValueError(f"mixing matrix must be square, got {Wn.shape}")
    L = Wn.shape[0]
    idx = np.arange(L)
    shifts = sorted(
        (s if s <= L // 2 else s - L)
        for s in range(1, L) if np.any(Wn[idx, (idx + s) % L] != 0))
    table = np.empty((L, len(shifts) + 1), dtype=Wn.dtype)
    table[:, 0] = np.diag(Wn)
    for k, s in enumerate(shifts):
        table[:, k + 1] = Wn[idx, (idx + s) % L]
    return tuple(shifts), table


@dataclasses.dataclass(frozen=True)
class RelabeledMeshWeights:
    """:func:`mesh_weights_from_matrix` after RCM shift-count pruning.

    ``perm`` (new→old) relabels the node axis; ``shifts``/``table``
    decompose the RELABELED matrix ``W[perm][:, perm]``.  A mesh run
    permutes its node-major inputs by ``perm`` (device k hosts old node
    ``perm[k]``), gossips with the pruned shift set, and un-permutes the
    outputs — the mixing arithmetic is identical (a relabeling is a
    similarity transform by a permutation matrix).  ``shifts_before`` /
    ``shifts_after`` report the pruning: each shift is one
    collective-permute per gossip round on the mesh runtime.
    """
    perm: np.ndarray
    shifts: tuple
    table: np.ndarray
    shifts_before: int
    shifts_after: int


def mesh_weights_relabeled(W, *, verify: bool = True
                           ) -> RelabeledMeshWeights:
    """Shift-count pruning for :func:`mesh_weights_from_matrix` via
    bandwidth-reducing node relabeling (reverse Cuthill–McKee on the
    mixing matrix's support).  An irregular graph's raw decomposition
    can need up to L−1 distinct cyclic shifts; RCM concentrates the
    support near the diagonal, so the relabeled matrix decomposes into
    the few shifts spanned by its bandwidth.  Falls back to the identity
    relabeling when RCM does not strictly reduce the shift count (e.g.
    a circulant is already optimal).  ``verify`` asserts round-trip
    equivalence: the shift table rebuilt densely must equal the
    relabeled matrix entry for entry, and un-permuting recovers W.
    """
    from repro.distributed.graphs import SparseGraph, reverse_cuthill_mckee
    from repro.distributed.mixing import SparseWeights
    if isinstance(W, SparseWeights):
        W = W.to_dense()  # reprolint: allow=RL002 — per-device mesh tier is small-L by construction; large-L uses VirtualTopology
    Wn = np.asarray(W)
    L = Wn.shape[0]
    shifts0, table0 = mesh_weights_from_matrix(Wn)
    off = (Wn != 0) | (Wn != 0).T
    np.fill_diagonal(off, False)
    rows, cols = np.nonzero(off)
    perm = reverse_cuthill_mckee(SparseGraph.from_edges(L, rows, cols))
    Wp = Wn[np.ix_(perm, perm)]
    shifts, table = mesh_weights_from_matrix(Wp)
    if len(shifts) >= len(shifts0):           # pruning didn't help
        perm, Wp = np.arange(L, dtype=np.int64), Wn
        shifts, table = shifts0, table0
    if verify:
        idx = np.arange(L)
        R = np.zeros_like(Wp)
        R[idx, idx] = table[:, 0]
        for k, s in enumerate(shifts):
            R[idx, (idx + s) % L] = table[:, k + 1]
        if not np.array_equal(R, Wp):
            raise AssertionError("RCM decomposition round-trip failed")
        inv = np.empty(L, dtype=np.int64)
        inv[perm] = np.arange(L)
        if not np.array_equal(Wp[np.ix_(inv, inv)], Wn):
            raise AssertionError("RCM relabeling round-trip failed")
    return RelabeledMeshWeights(perm=perm, shifts=tuple(shifts),
                                table=table, shifts_before=len(shifts0),
                                shifts_after=len(shifts))


# ----------------------------------------------------------------------
# virtual-node mesh tier
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class VirtualTopology:
    """Device × local-block decomposition of a sparse mixing matrix —
    the mesh form of the node axis past one-node-per-device.

    Node ``i`` lives on device ``i // block`` as local virtual node
    ``i % block`` (contiguous blocks).  Every stored edge falls in
    exactly one DEVICE-shift class ``s = (dev_j − dev_i) mod D`` — the
    per-edge-class generalization of :func:`mesh_weights_from_matrix`'s
    per-entry cyclic shifts:

      * class 0 (``local_*``): both endpoints co-located — gossip is a
        free on-device segment-sum shuffle, no wire traffic;
      * each nonzero class (``cross_*``, one slot per entry of
        ``dev_shifts``): ONE ``lax.ppermute`` of the whole local block
        per round, then a sparse apply at the receiver — only these
        classes pay priced bytes.

    Edge arrays are padded per device (dummy segment ``block``, weight
    exactly 0) and sorted by receiver row, so the on-device
    ``segment_sum`` jits with static shapes; ``diag`` is the separate
    (D, block) self-weight plane.  Topology is static metadata: all
    arrays are host numpy.
    """
    n_dev: int
    block: int
    dev_shifts: tuple[int, ...]
    local_rows: np.ndarray   # (D, E0) int32 — receiver local row
    local_cols: np.ndarray   # (D, E0) int32 — sender local row
    local_vals: np.ndarray   # (D, E0) float64
    cross_rows: np.ndarray   # (S, D, E1) int32
    cross_cols: np.ndarray   # (S, D, E1) int32 — sender-local, in the
    cross_vals: np.ndarray   # (S, D, E1)        permuted block
    diag: np.ndarray         # (D, block) float64

    @staticmethod
    def _group(dev, lr, lc, v, D: int, V: int):
        """Per-device padded (rows, cols, vals) — entries sorted by
        (device, local row) so segment ids are sorted, padding (row V,
        weight 0) at the end."""
        order = np.lexsort((lc, lr, dev))
        dev, lr, lc, v = dev[order], lr[order], lc[order], v[order]
        counts = np.bincount(dev, minlength=D)
        E = max(int(counts.max()) if counts.size else 0, 1)
        rows = np.full((D, E), V, np.int32)
        cols = np.zeros((D, E), np.int32)
        vals = np.zeros((D, E))
        starts = np.cumsum(counts) - counts
        pos = np.arange(dev.size) - np.repeat(starts, counts)
        rows[dev, pos] = lr
        cols[dev, pos] = lc
        vals[dev, pos] = v
        return rows, cols, vals

    @classmethod
    def from_weights(cls, W, n_dev: int) -> "VirtualTopology":
        from repro.distributed.mixing import SparseWeights
        sw = W if isinstance(W, SparseWeights) \
            else SparseWeights.from_dense(W)
        L, D = sw.n, int(n_dev)
        if D < 1 or L % D:
            raise ValueError(f"virtual-node tier needs n_dev to divide "
                             f"L, got L={L}, n_dev={D}")
        V = L // D
        di = (sw.rows // V).astype(np.int64)
        dj = (sw.cols // V).astype(np.int64)
        s = (dj - di) % D
        ss = np.where(s <= D // 2, s, s - D)
        lr = (sw.rows % V).astype(np.int64)
        lc = (sw.cols % V).astype(np.int64)
        loc = s == 0
        l_rows, l_cols, l_vals = cls._group(di[loc], lr[loc], lc[loc],
                                            sw.vals[loc], D, V)
        shifts = tuple(int(x) for x in np.unique(ss[~loc]))
        c_rows, c_cols, c_vals = [], [], []
        for sk in shifts:
            sel = ss == sk
            rk, ck, vk = cls._group(di[sel], lr[sel], lc[sel],
                                    sw.vals[sel], D, V)
            c_rows.append(rk)
            c_cols.append(ck)
            c_vals.append(vk)
        E1 = max((a.shape[1] for a in c_rows), default=1)

        def stack(arrs, fill, dtype):
            out = np.full((len(shifts), D, E1), fill, dtype)
            for k, a in enumerate(arrs):
                out[k, :, :a.shape[1]] = a
            return out
        return cls(
            n_dev=D, block=V, dev_shifts=shifts,
            local_rows=l_rows, local_cols=l_cols, local_vals=l_vals,
            cross_rows=stack(c_rows, V, np.int32),
            cross_cols=stack(c_cols, 0, np.int32),
            cross_vals=stack(c_vals, 0.0, np.float64),
            diag=np.asarray(sw.diag, np.float64).reshape(D, V).copy())

    # -------------------------------------------------------- accounting

    @property
    def n_nodes(self) -> int:
        return self.n_dev * self.block

    @property
    def n_local_entries(self) -> int:
        return int(np.count_nonzero(self.local_rows != self.block))

    @property
    def n_cross_entries(self) -> int:
        return int(np.count_nonzero(self.cross_rows != self.block))

    @property
    def block_sends_per_round(self) -> int:
        """ppermutes (whole-block sends) one round costs per device —
        the priced wire traffic; co-located gossip is free."""
        return len(self.dev_shifts)


def _device_slice(arrays, g):
    """This device's slice of :func:`virtual_arrays`'s stacked operands:
    ``(lr, lc, lv, [cr_k...], [cc_k...], [cv_k...], dg)`` — the selected
    form every virtual round variant (dense / masked / state) consumes,
    so the per-rule lowerings never re-derive the gather."""
    lr, lc, lv, cr, cc, cv, dg = arrays
    S = cr.shape[0]
    return (lr[g], lc[g], lv[g],
            [cr[k][g] for k in range(S)],
            [cc[k][g] for k in range(S)],
            [cv[k][g] for k in range(S)],
            dg[g])


def _virtual_selected_round(zf, vt: VirtualTopology, axis_name: str,
                            sel, *, z_diag=None):
    """One combine round on the virtual-node tier with PRE-SELECTED
    (possibly mask-folded or column-normalized) per-device edge arrays
    ``sel`` (:func:`_device_slice` layout).  ``zf: (V, F)`` is this
    device's flattened block — it is both the ppermute payload and the
    off-diagonal operand; ``z_diag`` (default ``zf``) is the operand of
    the diagonal term, split out for the compressed rules' exact-self
    correction (off-diagonal mass on the refreshed public copies, the
    self weight on the true iterate)."""
    lr, lc, lv, crs, ccs, cvs, dg = sel
    V, D = vt.block, vt.n_dev
    acc = dg[:, None] * (zf if z_diag is None else z_diag)
    acc = acc + jax.ops.segment_sum(
        lv[:, None] * zf[lc], lr, num_segments=V + 1,
        indices_are_sorted=True)[:V]
    for k, s in enumerate(vt.dev_shifts):
        perm = [(i, (i - s) % D) for i in range(D)]   # receive from i+s
        zs = jax.lax.ppermute(zf, axis_name, perm)
        acc = acc + jax.ops.segment_sum(
            cvs[k][:, None] * zs[ccs[k]], crs[k],
            num_segments=V + 1, indices_are_sorted=True)[:V]
    return acc


def virtual_mesh_round(zf, g, vt: VirtualTopology, axis_name: str,
                       arrays):
    """One gossip round on the virtual-node tier, ``zf: (V, F)`` this
    device's flattened block.  ``arrays`` are the device-side copies of
    vt's edge arrays in ``zf.dtype`` (built once per trace by
    :func:`virtual_arrays`)."""
    return _virtual_selected_round(zf, vt, axis_name,
                                   _device_slice(arrays, g))


def virtual_arrays(vt: VirtualTopology, dtype):
    """Device-side operands of :func:`virtual_mesh_round` (weights cast
    once to the iterate dtype)."""
    return (jnp.asarray(vt.local_rows), jnp.asarray(vt.local_cols),
            jnp.asarray(vt.local_vals, dtype),
            jnp.asarray(vt.cross_rows), jnp.asarray(vt.cross_cols),
            jnp.asarray(vt.cross_vals, dtype),
            jnp.asarray(vt.diag, dtype))


def _virtual_masked_fold(vt: VirtualTopology, sel, g, mf, *,
                         fold_diag: bool = True):
    """Edge-level availability fold on a device's selected arrays — the
    virtual-tier twin of :func:`_sparse_masked_fold`: a link is live iff
    BOTH endpoints are (receiver mask rows ``mf[g]``, sender mask rows
    ``mf[(g+s) mod D]`` per cross class), dead links' weight folds into
    the receiver's diagonal (``fold_diag=False`` keeps the original
    diagonal for push-sum, which renormalizes instead).  ``mf`` is the
    (D, V) per-device mask in the value dtype.  Padding entries carry
    weight exactly 0, so their clamped gathers contribute nothing."""
    lr, lc, lv, crs, ccs, cvs, dg = sel
    V, D = vt.block, vt.n_dev
    mg = mf[g]
    keep = mg[lr] * mg[lc]
    lv_m = lv * keep
    lost = jax.ops.segment_sum(lv * (1.0 - keep), lr,
                               num_segments=V + 1,
                               indices_are_sorted=True)[:V]
    cvs_m = []
    for k, s in enumerate(vt.dev_shifts):
        ms = mf[(g + s) % D]                    # the class's sender block
        keep_k = mg[crs[k]] * ms[ccs[k]]
        cvs_m.append(cvs[k] * keep_k)
        lost = lost + jax.ops.segment_sum(
            cvs[k] * (1.0 - keep_k), crs[k], num_segments=V + 1,
            indices_are_sorted=True)[:V]
    dg_eff = dg + lost if fold_diag else dg
    return (lr, lc, lv_m, crs, ccs, cvs_m, dg_eff)


def _vt_edges(vt: VirtualTopology):
    """Reconstruct the GLOBAL off-diagonal COO (rows, cols, vals) a
    VirtualTopology encodes, padding excluded — host-side metadata for
    structural checks (push-sum's symmetry validation)."""
    D, V = vt.n_dev, vt.block
    rows, cols, vals = [], [], []
    for g in range(D):
        live = vt.local_rows[g] != V
        rows.append(g * V + vt.local_rows[g][live])
        cols.append(g * V + vt.local_cols[g][live])
        vals.append(vt.local_vals[g][live])
    for k, s in enumerate(vt.dev_shifts):
        for g in range(D):
            live = vt.cross_rows[k, g] != V
            rows.append(g * V + vt.cross_rows[k, g][live])
            cols.append(((g + s) % D) * V + vt.cross_cols[k, g][live])
            vals.append(vt.cross_vals[k, g][live])
    return (np.concatenate(rows).astype(np.int64),
            np.concatenate(cols).astype(np.int64),
            np.concatenate(vals))


def _vt_is_symmetric(vt: VirtualTopology) -> bool:
    """Whether the encoded mixing matrix is symmetric: the sorted edge
    list equals the sorted transposed edge list (values to float
    tolerance) — O(E log E), never densified."""
    r, c, v = _vt_edges(vt)
    o1 = np.lexsort((c, r))       # (r, c) order of the edge list
    o2 = np.lexsort((r, c))       # (c, r) order = (r, c) of the transpose
    return (np.array_equal(r[o1], c[o2])
            and np.array_equal(c[o1], r[o2])
            and np.allclose(v[o1], v[o2]))


# ----------------------------------------------------------------------
# CombineRule
# ----------------------------------------------------------------------

class CombineRule:
    """One consensus/combine scheme, lowered three ways.

    ``make_sim_mixer(W, T_con, backend=...)`` returns the simulator
    closure ``Z (L, ...) ↦ combined Z``; ``make_mesh_mixer(...)`` the
    per-device closure used inside ``shard_map`` — pass ``W=`` for an
    arbitrary weighted topology (each distinct cyclic shift of W's
    sparsity pattern becomes one collective-permute, each device combines
    with its own W row), or ``shifts``/``self_weight`` for the uniform
    circulant form; ``signature(T_con)`` the comm cost.  Subclasses
    override the pieces that differ.
    """

    name: str = "base"

    # ------------------------------------------------------- simulator

    def make_sim_mixer(self, W, T_con: int, *,
                       backend: str = "xla-ref") -> Callable:
        raise NotImplementedError

    # ------------------------------------------------------------ mesh

    def make_mesh_mixer(self, axis_name: str, L: int, T_con: int,
                        shifts: Sequence[int] = (-1, 1),
                        self_weight: float | None = None, *,
                        W=None, backend: str = "xla-ref") -> Callable:
        raise NotImplementedError

    # ---------------------------------------------------- virtual mesh

    def make_virtual_mesh_mixer(self, axis_name: str,
                                vt: VirtualTopology, T_con: int, *,
                                backend: str = "xla-ref") -> Callable:
        raise NotImplementedError(
            f"combine rule {self.name!r} has no virtual-mesh lowering")

    # ------------------------------------------------------- signature

    def signature(self, T_con: int, **params) -> CommSignature:
        """The rule's per-iteration comm cost.  ``params`` carries the
        optional payload context (problem dims ``d``/``r`` and the
        compression knobs) — base rules ignore it; compressed rules use
        it to fill ``entries_per_round``/``bytes_per_entry``."""
        raise NotImplementedError

    # ---------------------------------------------------------- shared

    @staticmethod
    def _ring_weights(shifts: Sequence[int], self_weight: float | None):
        k = len(shifts)
        sw = self_weight if self_weight is not None else 1.0 / (k + 1)
        return sw, (1.0 - sw) / k

    @classmethod
    def _mesh_weights(cls, L: int, shifts: Sequence[int],
                      self_weight: float | None, W):
        """Resolve the mesh lowering's (shifts, weights) pair.

        With ``W``: decompose the actual mixing matrix — identical rows
        collapse to shared Python-float weights (the circulant fast
        path, no per-device gather), otherwise the full (L, K+1) table
        is kept and each device selects its row inside the round.
        Without ``W``: the historical uniform circulant weights of
        ``shifts``/``self_weight``."""
        if W is None:
            sw, wn = cls._ring_weights(shifts, self_weight)
            return tuple(shifts), (sw,) + (wn,) * len(shifts)
        shifts_, table = mesh_weights_from_matrix(W)
        if table.shape[0] != L:
            raise ValueError(f"mixing matrix is {table.shape[0]}×"
                             f"{table.shape[0]} but the mesh axis has "
                             f"{L} devices")
        if np.all(table == table[0]):
            return shifts_, tuple(float(x) for x in table[0])
        return shifts_, jnp.asarray(table)

    @classmethod
    def _mesh_round(cls, z, axis_name: str, L: int,
                    shifts: Sequence[int], weights, backend: str):
        """One gossip round on hardware: K collective-permutes to fetch
        neighbour blocks, then ONE (K+1)-way combine (fused on pallas
        backends).  ``weights`` is a shared scalar tuple (uniform /
        circulant) or an (L, K+1) table — then each device picks its own
        row by ``axis_index`` (arbitrary weighted topology)."""
        w = (weights if isinstance(weights, tuple)
             else weights[jax.lax.axis_index(axis_name)])
        nbrs = []
        for s in shifts:
            perm = [(i, (i - s) % L) for i in range(L)]   # receive from i+s
            nbrs.append(jax.lax.ppermute(z, axis_name, perm))
        return combine_blocks(z, nbrs, w, backend=backend)

    @classmethod
    def roll_round(cls, x, shifts: Sequence[int], weights, *,
                   backend: str = "xla-ref"):
        """One gossip round in the pjit/trainer form: neighbour blocks
        come from ``jnp.roll`` over the leading node axis (XLA lowers the
        sharded roll to the same collective-permute).  ``weights``:
        length-K+1 ``(w_self, w_shift1, ...)`` shared by every node, or a
        per-node ``(L, K+1)`` table (column k+1 = each node's weight on
        its shift-``shifts[k]`` neighbour — the
        :func:`mesh_weights_from_matrix` layout) for non-uniform /
        non-circulant mixing matrices."""
        nbrs = [jnp.roll(x, -s, axis=0) for s in shifts]
        w = jnp.asarray(weights) if not isinstance(weights, (tuple, list)) \
            else None
        if w is not None and w.ndim == 2:
            if w.shape[0] != x.shape[0]:
                raise ValueError(
                    f"per-node weight table has {w.shape[0]} rows but the "
                    f"leading node axis is {x.shape[0]} — roll_round mixes "
                    f"over the leading axis, one table row per node")
            # every node is a real row of the leading axis here, so the
            # table broadcasts directly; unfused chain in the promoted
            # accumulator dtype (the fused combine kernel takes only
            # per-shift scalars, not per-node tables)
            acc_dt = _acc_dtype(x.dtype)
            col = (slice(None),) + (None,) * (x.ndim - 1)
            wt = w.astype(acc_dt)
            acc = wt[:, 0][col] * x.astype(acc_dt)
            for k, nbr in enumerate(nbrs):
                acc = acc + wt[:, k + 1][col] * nbr.astype(acc_dt)
            return acc.astype(x.dtype)
        return combine_blocks(x, nbrs, weights, backend=backend)


class GossipCombine(CombineRule):
    """The paper's AGREE combine: T_con rounds of the mixing product
    ``Z ← W Z`` (Algorithm 1)."""

    name = "gossip"

    def make_sim_mixer(self, W, T_con: int, *, backend: str = "xla-ref"):
        from repro.distributed.mixing import SparseWeights
        W = maybe_sparsify(W)
        if T_con == 0:
            return lambda Z: Z
        if isinstance(W, SparseWeights):
            return self._make_sparse_sim_mixer(W, T_con, backend)
        if backend == "xla-ref" or W.dtype == jnp.float64:
            # sequential exact product: the unfused reference backend,
            # and x64 operands on any backend (deciding on W's dtype at
            # build time also keeps the dead f32 W^{T_con} hoist out of
            # x64 traces — reprolint rule JX003)
            return lambda Z: stacked_product(Z, W, T_con)
        Wp = jnp.linalg.matrix_power(W.astype(jnp.float32), T_con)

        def mix(Z):
            if Z.dtype == jnp.float64:
                # f32-accumulating fused kernel: keep x64 runs exact
                return stacked_product(Z, W, T_con)
            return stacked_dense_mix(Z, Wp, backend=backend)
        return mix

    @staticmethod
    def _make_sparse_sim_mixer(sw, T_con: int, backend: str):
        """Sparse twin of the hoist policy: fused backends precompute
        ``W^{T_con}`` host-side (scipy CSR power) and apply it in ONE
        segment-sum round — but only while the power's fill-in stays
        within :meth:`SparseWeights.power`'s budget; past it (or on
        xla-ref / f64 operands, which stay sequential-exact) the mixer
        degrades gracefully to the per-round sparse product."""
        hoisted = None
        if backend != "xla-ref" and T_con > 1:
            hoisted = sw.power(T_con)     # None → fill-in over budget

        def mix(Z):
            if (hoisted is None or backend == "xla-ref"
                    or Z.dtype == jnp.float64):
                return stacked_sparse_product(Z, sw, T_con)
            return stacked_sparse_product(Z, hoisted, 1)
        return mix

    def make_mesh_mixer(self, axis_name, L, T_con, shifts=(-1, 1),
                        self_weight=None, *, W=None, backend="xla-ref"):
        shifts_, weights = self._mesh_weights(L, shifts, self_weight, W)
        if T_con == 0:
            return lambda z: z

        def gossip(z):
            def round_(carry, _):
                return self._mesh_round(carry, axis_name, L, shifts_,
                                        weights, backend), None
            out, _ = jax.lax.scan(round_, z, None, length=T_con)
            return out
        return gossip

    def make_virtual_mesh_mixer(self, axis_name: str,
                                vt: VirtualTopology, T_con: int, *,
                                backend: str = "xla-ref") -> Callable:
        """Per-device closure ``z (V, ...) ↦ z'`` on the virtual-node
        tier: T_con rounds, each one on-device segment-sum shuffle for
        the co-located edges plus one ppermute + sparse apply per
        cross-device shift class.  Always per-round (a ``W^{T_con}``
        hoist would create new cross-device classes, defeating the
        decomposition)."""
        if T_con == 0:
            return lambda z: z

        def gossip(z):
            g = jax.lax.axis_index(axis_name)
            arrays = virtual_arrays(vt, z.dtype)
            shape = z.shape

            def round_(carry, _):
                out = virtual_mesh_round(carry, g, vt, axis_name, arrays)
                return out, None
            out, _ = jax.lax.scan(round_, z.reshape(vt.block, -1), None,
                                  length=T_con)
            return out.reshape(shape)
        return gossip

    def signature(self, T_con: int, **params) -> CommSignature:
        return CommSignature("gossip", T_con)


class NeighborCombine(CombineRule):
    """DGD's combine: ONE row-stochastic neighbour average that excludes
    the node itself (Experiment 1's ``(1/deg_g) Σ_{g'∈N_g} U_g'``).  The
    simulator form takes the precomputed neighbour-average matrix M."""

    name = "neighbor"

    def make_sim_mixer(self, M, T_con: int = 1, *, backend: str = "xla-ref"):
        M = maybe_sparsify(M)
        return lambda Z: stacked_dense_mix(Z, M, backend=backend)

    def make_mesh_mixer(self, axis_name, L, T_con=1, shifts=(-1, 1),
                        self_weight=None, *, W=None, backend="xla-ref"):
        """ONE neighbour-average round.  Without ``W`` the circulant
        graph of ``shifts`` is K-regular, so the average is the
        equal-weight shift combine with structurally zero self weight;
        with ``W`` (the precomputed row-stochastic neighbour matrix,
        zero diagonal) each device combines with its own row — the
        irregular-graph form."""
        if W is None:
            shifts_ = tuple(shifts)
            weights = (0.0,) + (1.0 / len(shifts),) * len(shifts)
        else:
            shifts_, weights = self._mesh_weights(L, shifts, self_weight, W)
        return lambda z: self._mesh_round(z, axis_name, L, shifts_,
                                          weights, backend)

    def make_virtual_mesh_mixer(self, axis_name: str,
                                vt: VirtualTopology, T_con: int = 1, *,
                                backend: str = "xla-ref") -> Callable:
        """ONE neighbour-average round on the virtual tier, whatever
        ``T_con`` says (the rule IS a single self-excluding exchange).
        ``vt`` decomposes the precomputed row-stochastic neighbour
        matrix — its zero diagonal survives the decomposition as a zero
        ``diag`` plane, so the round is exactly ``M Z``."""
        def mix(z):
            g = jax.lax.axis_index(axis_name)
            arrays = virtual_arrays(vt, z.dtype)
            shape = z.shape
            out = virtual_mesh_round(z.reshape(vt.block, -1), g, vt,
                                     axis_name, arrays)
            return out.reshape(shape)
        return mix

    def signature(self, T_con: int, **params) -> CommSignature:
        return CommSignature("neighbor", 1)


class CentralCombine(CombineRule):
    """Fusion-center combine: the exact node mean (AltGDmin [10])."""

    name = "central"

    def make_sim_mixer(self, W=None, T_con: int = 0, *,
                       backend: str = "xla-ref"):
        return node_mean

    def make_mesh_mixer(self, axis_name, L, T_con=0, shifts=(),
                        self_weight=None, *, W=None, backend="xla-ref"):
        return lambda z: jax.lax.pmean(z, axis_name)

    def signature(self, T_con: int, **params) -> CommSignature:
        return CommSignature("central", 1)


class NoCombine(CombineRule):
    """Local training: no communication (identity combine)."""

    name = "none"

    def make_sim_mixer(self, W=None, T_con: int = 0, *,
                       backend: str = "xla-ref"):
        return lambda Z: Z

    def make_mesh_mixer(self, axis_name, L, T_con=0, shifts=(),
                        self_weight=None, *, W=None, backend="xla-ref"):
        return lambda z: z

    def signature(self, T_con: int, **params) -> CommSignature:
        return CommSignature("none", 0)


class ExactDiffusionCombine(GossipCombine):
    """The projection-corrected combine of Exact Subspace Diffusion
    (arXiv:2304.07358).  The mixing product is standard AGREE, but each
    application first bias-corrects the adapt iterate with the previous
    correction state:

        φ_g^τ = ψ_g^τ + U_g^{τ-1} − ψ_g^{τ-1}        (correction)
        Ũ_g^τ = Σ_j W_gj φ_j^τ  (T_con rounds)        (combine)

    so the combine tracks the exact (bias-free) fixed point instead of
    the diffusion limit point; the driver carries ``(ψ_prev, U_prev)``
    through its scan and retracts Ũ onto the Grassmannian afterwards
    (the subspace projection step).
    """

    name = "exact_diffusion"

    @staticmethod
    def correct(psi, psi_prev, U_prev):
        """φ = ψ + U_prev − ψ_prev (vanishes at τ=0 when ψ_prev=U_prev)."""
        return psi + U_prev - psi_prev


class BeyondCentralCombine(GossipCombine):
    """The communication-efficient combine of Beyond Centralization
    (arXiv:2512.22675): nodes take several *local* adapt steps between
    consensus exchanges and then combine with ONE gossip round — per
    outer iteration the wire carries a single d×r exchange instead of
    the T_con-round AGREE chain."""

    name = "beyond_central"

    def make_sim_mixer(self, W, T_con: int = 1, *, backend: str = "xla-ref"):
        # a single mixing round regardless of T_con — that IS the rule
        return super().make_sim_mixer(W, 1, backend=backend)

    def make_mesh_mixer(self, axis_name, L, T_con=1, shifts=(-1, 1),
                        self_weight=None, *, W=None, backend="xla-ref"):
        return super().make_mesh_mixer(axis_name, L, 1, shifts,
                                       self_weight, W=W, backend=backend)

    def make_virtual_mesh_mixer(self, axis_name, vt, T_con=1, *,
                                backend="xla-ref"):
        return super().make_virtual_mesh_mixer(axis_name, vt, 1,
                                               backend=backend)

    def signature(self, T_con: int, **params) -> CommSignature:
        return CommSignature("gossip", 1)


# ----------------------------------------------------------------------
# compressed / event-triggered wire rules
# ----------------------------------------------------------------------

def _scatter_replace_rows(xhat, vals, idx):
    """Replace rows ``idx`` of each (d, r) block with ``vals`` (top-k
    refresh).  Indices from top-k are unique, so the scatter is
    order-independent and a FULL index set makes the result exactly
    ``vals``'s source — the bit-identity anchor of ``k = d``."""
    def one(x, v, i):
        return x.at[i].set(v)
    return jax.vmap(one)(xhat, vals, idx)


class CompressedGossipCombine(GossipCombine):
    """Base of the compressed-communication gossip rules.

    These rules shrink what one gossip round puts on the wire.  Naive
    compression of the d×r iterate itself stalls far from the dense
    trajectory (an orthonormal-ish basis has no dominant rows to keep),
    so the rules use the reference-copy error-feedback scheme of
    CHOCO-SGD / EF21: every node maintains a PUBLIC COPY ``x̂_g`` of its
    iterate — the value the network believes — replicated at its
    neighbours, and each round refreshes the copy's stalest content with
    a compact payload:

        payload, x̂_g' = refresh(Z_g, x̂_g)      # what crosses the wire
        x̂_j'          = apply(payload_j, x̂_j)  # neighbours' copies
        Z_g'           = W_gg·Z_g + Σ_{j≠g} W_gj·x̂_j'

    The copy state IS the error-feedback state: ``Z − x̂`` is exactly
    the accumulated compression error, re-injected into every
    subsequent payload, and it contracts as consensus tightens — so
    compressed Dif-AltGDmin still converges to the paper's error floor.
    The drivers thread the state through their ``lax.scan`` carry (the
    mesh runtime's aux-carry slot).

    The SELF term never crosses a wire, so the combine keeps it exact:
    the simulator computes ``W @ X̂' + diag(W)·(Z − X̂')`` (one dense
    combine on the refreshed copies — fused ``mix_rows`` on pallas
    backends — plus the exact-self correction); the mesh ppermutes the
    COMPACT payload per shift, applies it to the stored neighbour
    copies, and merges the K+1 blocks in ONE fused ``gossip_combine``
    dispatch per round.  A lossless refresh (k = d, θ = 0) makes
    ``X̂' = Z`` bit-exact and the round IS the dense ``W @ Z`` product
    bit-for-bit on the exact (unfused / x64) lowering — the numerics
    anchor the tests pin.  Fused backends agree with the dense rule to
    f32 round-off only: dense gossip hoists all T_con rounds into ONE
    precomputed ``W^{T_con}`` combine, while a compressed rule must mix
    round by round (the refresh is data-dependent).

    Precision policy (the shared ``_fused_wanted`` gate): float64
    operands take the exact reference encoder AND the unfused combine
    chain — compression *semantics* are dtype-independent, only the
    f32-accumulating kernels are avoided, so x64 runs stay exact.

    The stateless ``make_sim_mixer``/``make_mesh_mixer`` entry points
    are forbidden (they would silently drop the state); drivers use
    ``make_sim_state_mixer``/``make_mesh_state_mixer`` and seed the
    state with ``init_state`` (simulator) / ``init_mesh_state`` (one
    copy of every neighbour's x̂ per device, zero-initialized on both
    substrates so the copies agree without a setup exchange).
    """

    # ------------------------------------------------- rule interface

    def resolve_params(self, d: int, r: int, **kw) -> dict:
        """Static per-run parameters from the spec knobs + problem dims."""
        raise NotImplementedError

    def refresh(self, Z, xhat, node_ids, count, *, backend, **params):
        """One round's wire encode for stacked blocks ``Z: (N, d, r)``:
        returns ``(payload, xhat_new)`` — the compact payload that
        crosses the wire and the node's refreshed public copy."""
        raise NotImplementedError

    def apply(self, payload, xhat, *, backend, **params):
        """A receiver's side of ``refresh``: update a stored neighbour
        copy ``xhat: (N, d, r)`` from a received payload.  Must
        reproduce ``refresh``'s ``xhat_new`` bit-for-bit given the same
        payload and copy (simulator ≡ mesh parity rests on it)."""
        raise NotImplementedError

    # ------------------------------------------------------- state

    def init_state(self, Z_nodes, **kw):
        """Simulator state: the stacked public copies ``x̂`` (zero — the
        network starts with no beliefs), plus the round counter for
        stochastic rules."""
        xhat = jnp.zeros_like(Z_nodes)
        if self._stochastic(**kw):
            return (xhat, jnp.zeros((), jnp.int32))
        return xhat

    def init_mesh_state(self, z_local, n_shifts: int, **kw):
        """Per-device mesh state: ``(x̂_self (1, d, r), x̂_nbrs
        (n_shifts, 1, d, r))`` — this device's public copy plus its copy
        of each shift-neighbour's x̂ (what the neighbour's payloads have
        built up), all zero-initialized."""
        own = jnp.zeros_like(z_local[None])
        # broadcast from ``own`` so the buffers inherit z_local's
        # varying-axes type inside shard_map (the scan carry needs it)
        nbrs = jnp.broadcast_to(own, (n_shifts,) + own.shape)
        if self._stochastic(**kw):
            return (own, nbrs, jnp.zeros((), jnp.int32))
        return own, nbrs

    def _stochastic(self, **kw) -> bool:
        return False

    # ----------------------------------------------------- lowerings

    def make_sim_mixer(self, W, T_con, *, backend="xla-ref"):
        raise TypeError(f"combine rule {self.name!r} is stateful; use "
                        f"make_sim_state_mixer / init_state")

    def make_mesh_mixer(self, axis_name, L, T_con, shifts=(-1, 1),
                        self_weight=None, *, W=None, backend="xla-ref"):
        raise TypeError(f"combine rule {self.name!r} is stateful; use "
                        f"make_mesh_state_mixer / init_mesh_state")

    def make_virtual_mesh_mixer(self, axis_name, vt, T_con, *,
                                backend="xla-ref"):
        raise TypeError(f"combine rule {self.name!r} is stateful; use "
                        f"make_virtual_mesh_state_mixer / init_state")

    def make_sim_state_mixer(self, W, T_con: int, *,
                             backend: str = "xla-ref", **kw) -> Callable:
        """Simulator closure ``(Z (L, d, r), state) ↦ (Z', state')``:
        T_con rounds of refresh + dense combine on the public copies +
        exact-self correction.  ``consensus_gamma`` (CHOCO step size,
        default 1) relaxes each round toward the combined value,
        ``Z ← Z + γ(combined − Z)`` — the damping that keeps aggressive
        compression (k ≪ d/4) stable; γ = 1 is a Python-level no-op so
        default trajectories stay bit-identical."""
        from repro.distributed.mixing import SparseWeights
        gamma = float(kw.pop("consensus_gamma", 1.0))
        W = maybe_sparsify(W)
        sparse = isinstance(W, SparseWeights)
        if T_con == 0:
            return lambda Z, state: (Z, state)

        def mix(Z, state):
            N = Z.shape[0]
            params = self.resolve_params(Z.shape[1], Z.shape[2], **kw)
            ids = jnp.arange(N)
            if sparse:
                rows, cols, vals, diag = _sparse_arrays(W)
                rows, cols = jnp.asarray(rows), jnp.asarray(cols)
                vals = jnp.asarray(vals, Z.dtype)
                w_diag = jnp.asarray(diag, Z.dtype)[:, None, None]
            else:
                # host-side: W is concrete here, and jnp.diag would
                # trace an (L, L) iota mask
                w_diag = jnp.asarray(np.diag(np.asarray(W)),
                                     Z.dtype)[:, None, None]

            def round_(carry, _):
                Zc, st = carry
                xhat, count = st if self._stochastic(**kw) else (st, None)
                _, xhat2 = self.refresh(Zc, xhat, ids, count,
                                        backend=backend, **params)
                if sparse:
                    # exact-self built in: (W − diag) x̂' + diag·Z equals
                    # the dense W x̂' + diag·(Z − x̂') without the
                    # add-and-subtract round trip
                    off = sparse_offdiag_apply(xhat2.reshape(N, -1),
                                               rows, cols, vals, N)
                    Z2 = off.reshape(Zc.shape) + w_diag * Zc
                    if gamma != 1.0:
                        Z2 = Zc + gamma * (Z2 - Zc)
                    st2 = ((xhat2, count + 1) if self._stochastic(**kw)
                           else xhat2)
                    return (Z2, st2), None
                if _fused_wanted(backend, Zc.dtype):
                    Z2 = stacked_dense_mix(xhat2, W, backend=backend)
                else:
                    # dense product on the refreshed copies, arithmetic-
                    # identical to stacked_product's round
                    Z2 = (W.astype(Zc.dtype)
                          @ xhat2.reshape(N, -1)).reshape(Zc.shape)
                # exact-self correction: the node's own block never
                # crosses a wire.  A lossless refresh (k = d, θ = 0)
                # makes Zc − xhat2 exactly zero, so the round stays the
                # dense W @ Z product bit-for-bit.
                Z2 = Z2 + w_diag * (Zc - xhat2)
                if gamma != 1.0:
                    Z2 = Zc + gamma * (Z2 - Zc)      # CHOCO relaxation
                st2 = ((xhat2, count + 1) if self._stochastic(**kw)
                       else xhat2)
                return (Z2, st2), None

            (Z_fin, st_fin), _ = jax.lax.scan(round_, (Z, state), None,
                                              length=T_con)
            return Z_fin, st_fin
        return mix

    def make_mesh_state_mixer(self, axis_name: str, L: int, T_con: int,
                              shifts: Sequence[int] = (-1, 1),
                              self_weight: float | None = None, *,
                              W=None, backend: str = "xla-ref",
                              **kw) -> Callable:
        """Per-device closure ``(z (d, r), state) ↦ (z', state')`` with
        ``state = (x̂_self, x̂_nbrs[, count])`` from ``init_mesh_state``:
        per round the COMPACT payload is exchanged by collective-permute
        (one per distinct cyclic shift), applied to the stored neighbour
        copies, and the K+1 blocks — exact self + refreshed copies —
        merge in ONE fused ``gossip_combine`` dispatch.
        ``consensus_gamma``: the CHOCO relaxation, as on the simulator
        lowering (γ = 1 → bit-identical no-op)."""
        gamma = float(kw.pop("consensus_gamma", 1.0))
        shifts_, weights = self._mesh_weights(L, shifts, self_weight, W)
        if T_con == 0:
            return lambda z, state: (z, state)

        def mix(z, state):
            d, r = z.shape
            params = self.resolve_params(d, r, **kw)
            ids = jax.lax.axis_index(axis_name)[None]
            w = (weights if isinstance(weights, tuple)
                 else weights[jax.lax.axis_index(axis_name)])

            def round_(carry, _):
                zc, st = carry
                if self._stochastic(**kw):
                    own, nbr_copies, count = st
                else:
                    (own, nbr_copies), count = st, None
                payload, own2 = self.refresh(zc[None], own, ids, count,
                                             backend=backend, **params)
                nbrs2 = []
                for i, s in enumerate(shifts_):
                    perm = [(g, (g - s) % L) for g in range(L)]
                    p = jax.tree.map(
                        lambda x: jax.lax.ppermute(x, axis_name, perm),
                        payload)
                    nbrs2.append(self.apply(p, nbr_copies[i],
                                            backend=backend, **params))
                # exact-self combine: the device's own block goes in
                # exact, neighbours as their refreshed public copies
                z2 = combine_blocks(zc, [n[0] for n in nbrs2], w,
                                    backend=backend)
                if gamma != 1.0:
                    z2 = zc + gamma * (z2 - zc)      # CHOCO relaxation
                nbr2 = (jnp.stack(nbrs2) if nbrs2
                        else jnp.zeros_like(nbr_copies))
                st2 = ((own2, nbr2, count + 1)
                       if self._stochastic(**kw) else (own2, nbr2))
                return (z2, st2), None

            (z_fin, st_fin), _ = jax.lax.scan(round_, (z, state), None,
                                              length=T_con)
            return z_fin, st_fin
        return mix

    def make_virtual_mesh_state_mixer(self, axis_name: str, vt, T_con: int,
                                      *, backend: str = "xla-ref",
                                      **kw) -> Callable:
        """Per-device virtual-tier closure ``(z (V, d, r), state) ↦
        (z', state')`` with ``state`` the block's stacked public copies
        from ``init_state`` (zero, per virtual node).  Each round
        refreshes the block's copies — GLOBAL node ids ``g·V + [0, V)``
        keep the stochastic quantizer's per-node fold_in identical to
        the simulator's ``arange(L)`` — then runs one sparse segment-sum
        round on the refreshed copies with the diagonal applied to the
        EXACT iterate (the simulator's exact-self identity ``(W − diag)
        x̂' + diag·Z``).  The wire note: a cross-device shift class
        ships the whole refreshed block; the per-edge payload is still
        the compact refresh semantically, the block transport just
        batches it.  ``consensus_gamma`` relaxes as on the other
        lowerings (γ = 1 → no-op)."""
        gamma = float(kw.pop("consensus_gamma", 1.0))
        if T_con == 0:
            return lambda z, state: (z, state)

        def mix(z, state):
            V = vt.block
            params = self.resolve_params(z.shape[1], z.shape[2], **kw)
            g = jax.lax.axis_index(axis_name)
            ids = g * V + jnp.arange(V)
            arrays = virtual_arrays(vt, z.dtype)
            sel = _device_slice(arrays, g)

            def round_(carry, _):
                zc, st = carry
                xhat, count = st if self._stochastic(**kw) else (st, None)
                _, xhat2 = self.refresh(zc, xhat, ids, count,
                                        backend=backend, **params)
                acc = _virtual_selected_round(
                    xhat2.reshape(V, -1), vt, axis_name, sel,
                    z_diag=zc.reshape(V, -1))
                Z2 = acc.reshape(zc.shape)
                if gamma != 1.0:
                    Z2 = zc + gamma * (Z2 - zc)      # CHOCO relaxation
                st2 = ((xhat2, count + 1) if self._stochastic(**kw)
                       else xhat2)
                return (Z2, st2), None

            (z_fin, st_fin), _ = jax.lax.scan(round_, (z, state), None,
                                              length=T_con)
            return z_fin, st_fin
        return mix


class TopkGossipCombine(CompressedGossipCombine):
    """``topk_gossip`` — rank-preserving top-k ROW refresh: per round
    each node re-broadcasts the ``compression_k`` rows of its iterate
    whose public copy drifted the most (largest ``‖Z − x̂‖`` row norms —
    the ``compress_topk`` kernel selects, the wire carries the ABSOLUTE
    ``Z`` rows + int32 indices, receivers replace those copy rows).
    Keeping whole rows keeps the payload a valid factor slice;
    ``compression_k = 0`` defaults to d/4 (a 4× value-entry reduction);
    ``compression_k = d`` refreshes every row and recovers dense gossip
    bit-identically on the exact path (see the base-class note on fused
    backends).

    Wire-format pricing: the signature prices k·r payload values at
    4 bytes (f32 — a sparsified payload does not carry the simulation's
    f64, since the production combine accumulates in f32 anyway) plus k
    int32 row indices, against the dense baseline at the network
    model's native precision.  At k = d/4 under the paper's f64 model
    the 6.4× bytes reduction therefore decomposes as 3.2× from sending
    fewer entries × 2× from the f32 wire; ``bench_compression`` reports
    both factors separately."""

    name = "topk_gossip"

    def resolve_params(self, d, r, compression_k: int = 0, **_):
        k = int(compression_k) or max(1, d // 4)
        if not 1 <= k <= d:
            raise ValueError(f"topk_gossip needs 1 <= compression_k <= d, "
                             f"got k={k} for d={d}")
        return {"k": k}

    def refresh(self, Z, xhat, node_ids, count, *, backend, k):
        from repro.kernels import ops
        delta = Z - xhat                     # accumulated compression error
        cb = backend if _fused_wanted(backend, Z.dtype) else "xla-ref"
        _, idx = ops.compress_topk(delta, k, backend=cb)   # stalest rows
        vals = jnp.take_along_axis(Z, idx[..., None], axis=1)
        return (vals, idx), _scatter_replace_rows(xhat, vals, idx)

    def apply(self, payload, xhat, *, backend, k):
        vals, idx = payload
        return _scatter_replace_rows(xhat, vals, idx)

    def signature(self, T_con: int, *, d=None, r=None, compression_k=0,
                  **_) -> CommSignature:
        if d is None or r is None:
            return CommSignature("gossip", T_con)
        k = self.resolve_params(d, r, compression_k)["k"]
        # f32 wire values (k·r) + int32 row indices (k): 4 bytes each
        return CommSignature("gossip", T_con,
                             entries_per_round=k * (r + 1),
                             bytes_per_entry=4)


class QuantizedGossipCombine(CompressedGossipCombine):
    """``quantized_gossip`` — low-precision wire dtype with f32
    accumulation: the DIFFERENCE ``Z − x̂`` is quantized and accumulated
    onto the public copies, so the quantization error contracts with
    consensus (exact convergence, no bf16-resolution floor on the
    iterate itself).  Wire formats (``compression``):

      * ``"bf16"`` (default) — round-to-nearest-even bfloat16 cast;
        2 bytes/entry, no side information;
      * ``"int8"`` — per-message max-abs scale, round-to-nearest int8;
        1 byte/entry + one f32 scale per message;
      * ``"int8_stochastic"`` — int8 with stochastic rounding
        (deterministic counter-based keys: the same per-node draws on
        both substrates, so simulator ≡ mesh parity holds bit-wise).

    The combine itself always accumulates in f32 (or f64 on the exact
    x64 path) — only the wire carries the low-precision payload.
    """

    name = "quantized_gossip"

    WIRES = ("bf16", "int8", "int8_stochastic")

    def resolve_params(self, d, r, compression=None, **_):
        wire = compression or "bf16"
        if wire not in self.WIRES:
            raise ValueError(f"unknown quantized_gossip wire format "
                             f"{wire!r}; expected one of {self.WIRES}")
        return {"wire": wire}

    def _stochastic(self, compression=None, **_):
        return (compression or "bf16") == "int8_stochastic"

    @staticmethod
    def _int8_scale(delta):
        scale = jnp.max(jnp.abs(delta), axis=(-2, -1), keepdims=True) / 127.0
        return jnp.maximum(scale, jnp.finfo(delta.dtype).tiny)

    def _dequant(self, q, scale, dtype, *, backend):
        from repro.kernels import ops
        cb = backend if _fused_wanted(backend, dtype) else "xla-ref"
        return ops.dequant(q, scale, backend=cb)

    def refresh(self, Z, xhat, node_ids, count, *, backend, wire):
        delta = Z - xhat                     # accumulated compression error
        if wire == "bf16":
            q = delta.astype(jnp.bfloat16)
            payload = (q,)
            inc = q.astype(Z.dtype)
        else:
            scale = self._int8_scale(delta)
            if wire == "int8_stochastic":
                key = jax.random.fold_in(jax.random.PRNGKey(0), count)
                keys = jax.vmap(jax.random.fold_in, (None, 0))(key, node_ids)
                # dither in the operand precision: drawing at f32 and
                # upcasting would narrow x64 runs (reprolint JX003)
                u = jax.vmap(lambda kk: jax.random.uniform(
                    kk, Z.shape[1:], Z.dtype))(keys)
                qf = jnp.floor(delta / scale + u)
            else:
                qf = jnp.rint(delta / scale)
            q = jnp.clip(qf, -127, 127).astype(jnp.int8)
            payload = (q, scale)
            inc = self._dequant(q, scale, Z.dtype, backend=backend)
        return payload, xhat + inc

    def apply(self, payload, xhat, *, backend, wire):
        if wire == "bf16":
            return xhat + payload[0].astype(xhat.dtype)
        q, scale = payload
        return xhat + self._dequant(q, scale, xhat.dtype, backend=backend)

    def signature(self, T_con: int, *, d=None, r=None, compression=None,
                  **_) -> CommSignature:
        if d is None or r is None:
            return CommSignature("gossip", T_con)
        wire = self.resolve_params(d, r, compression)["wire"]
        if wire == "bf16":
            return CommSignature("gossip", T_con, entries_per_round=d * r,
                                 bytes_per_entry=2)
        # int8 payload + one f32 scale (4 one-byte entries)
        return CommSignature("gossip", T_con, entries_per_round=d * r + 4,
                             bytes_per_entry=1)


class EventGossipCombine(CompressedGossipCombine):
    """``event_gossip`` — event-triggered exchange: a node re-broadcasts
    its full iterate only when its public copy went stale,
    ``‖Z_g − x̂_g‖_F > θ·‖Z_g‖_F`` (θ = ``event_threshold``); otherwise
    neighbours keep combining with the last-sent copy.  θ = 0 always
    triggers and recovers dense gossip bit-identically on the exact
    path (see the base-class note on fused backends).

    The SPMD lowerings still execute the exchange every round (a static
    program cannot elide a send), so the saving is a *message-count*
    one on real event-driven networks; the static signature therefore
    prices the θ = 0 worst case, and ``benchmarks.kernel_bench.
    bench_compression`` reports the measured send fraction."""

    name = "event_gossip"

    def resolve_params(self, d, r, event_threshold: float = 0.0, **_):
        if event_threshold < 0:
            raise ValueError(f"event_threshold must be >= 0, got "
                             f"{event_threshold}")
        return {"threshold": float(event_threshold)}

    @staticmethod
    def _trigger(Z, xhat, threshold):
        """Per-node send decision: ``‖Z − x̂‖_F > θ·‖Z‖_F`` — ONE
        definition shared by the round encode and the benchmark
        telemetry, so the reported send fraction always measures the
        condition the rule actually uses."""
        moved = jnp.sqrt(jnp.sum((Z - xhat) ** 2, axis=(-2, -1)))
        scale = jnp.sqrt(jnp.sum(Z ** 2, axis=(-2, -1)))
        return moved > threshold * scale

    def refresh(self, Z, xhat, node_ids, count, *, backend, threshold):
        trig = self._trigger(Z, xhat, threshold)
        S = jnp.where(trig[:, None, None], Z, xhat)    # absolute resend
        return (S,), S

    def apply(self, payload, xhat, *, backend, threshold):
        return payload[0]

    def send_fraction(self, Z, xhat, threshold: float):
        """Measured trigger rate of one round (benchmark telemetry —
        the static signature prices the worst case instead)."""
        return jnp.mean(self._trigger(Z, xhat, threshold)
                        .astype(jnp.float32))

    def signature(self, T_con: int, **_) -> CommSignature:
        # static pricing cannot see the trigger rate: θ = 0 worst case
        return CommSignature("gossip", T_con)

# ----------------------------------------------------------------------
# dropout-tolerant rules (availability-masked gossip)
# ----------------------------------------------------------------------

def masked_mixing_matrix(W, mask):
    """Per-round effective mixing matrix under a participation mask
    ``mask: (L,)`` (truthy = live).  A link is live iff BOTH endpoints
    are; a dead link's weight folds back into the SELF weight (mass
    redistribution over the live neighbourhood rather than row division),
    which (a) keeps W(m) doubly stochastic whenever W is — so partial
    gossip stays an unbiased averaging operator in expectation — and
    (b) makes the full mask return W bit-for-bit (multiply by exact
    ones, add exact zeros): the degenerate regression anchor.  A fully
    isolated down node's row degenerates to ``e_g`` (its lost weight is
    its whole off-diagonal mass), freezing its iterate."""
    m = mask.astype(W.dtype)
    eye = jnp.eye(W.shape[0], dtype=W.dtype)
    keep = m[:, None] * m[None, :] * (1.0 - eye) + eye   # self link stays
    lost = jnp.sum(W * (1.0 - keep), axis=1)
    return W * keep + jnp.diag(lost)


def push_sum_matrix(W, mask):
    """Column-stochastic masked mixing matrix for push-sum: live sender
    j distributes its mass over its LIVE out-neighbours + itself, each
    column renormalized by its live mass ``c_j = W_jj + Σ_{i≠j} m_i m_j
    W_ij`` — exactly column-stochastic by construction, whatever the
    mask does to the graph (the directed, non-doubly-stochastic regime
    push-sum's weight carry corrects)."""
    m = mask.astype(W.dtype)
    eye = jnp.eye(W.shape[0], dtype=W.dtype)
    keep = m[:, None] * m[None, :] * (1.0 - eye) + eye
    Wm = W * keep
    c = jnp.sum(Wm, axis=0)                              # live column mass
    return Wm / jnp.where(c > 0, c, 1.0)[None, :]


def _sparse_masked_fold(rows, cols, vals, diag, m, L: int):
    """Edge-level :func:`masked_mixing_matrix`: a link is live iff BOTH
    endpoints are (``keep = m_i · m_j`` per stored edge), and a dead
    link's weight folds into the receiver's diagonal.  Padding entries
    carry weight exactly 0, so their out-of-bounds row-L gathers (jnp
    clamps them) contribute nothing to either term."""
    keep = m[rows] * m[cols]
    lost = jax.ops.segment_sum(vals * (1.0 - keep), rows,
                               num_segments=L + 1,
                               indices_are_sorted=True)[:L]
    return vals * keep, diag + lost


def _sparse_masked_gossip_mixer(sw, T_con: int):
    """Sparse lowering of ``partial_gossip``'s simulator mixer: fold the
    mask once per iteration, then T_con segment-sum rounds.  The fold is
    data-dependent, so there is no ``W^{T_con}`` hoist on any backend
    (exactly like the dense lowering)."""
    rows_h, cols_h, vals_h, diag_h = _sparse_arrays(sw)
    L = sw.n

    def mix(Z, m):
        rows, cols = jnp.asarray(rows_h), jnp.asarray(cols_h)
        vals = jnp.asarray(vals_h, Z.dtype)
        diag = jnp.asarray(diag_h, Z.dtype)
        vals_eff, diag_eff = _sparse_masked_fold(
            rows, cols, vals, diag, m.astype(Z.dtype), L)
        flat = Z.reshape(L, -1)

        def round_(carry, _):
            return sparse_round(carry, rows, cols, vals_eff, diag_eff,
                                L), None
        out, _ = jax.lax.scan(round_, flat, None, length=T_con)
        return out.reshape(Z.shape)
    return mix


class MaskedGossipCombine(GossipCombine):
    """Base of the dropout-tolerant gossip rules: per-iteration
    availability masks enter the combine, so the stateless
    ``make_sim_mixer``/``make_mesh_mixer`` entry points are forbidden
    (they would silently drop the mask) — drivers use the
    ``*_masked_*`` forms and pass the (L,) mask of the CURRENT outer
    iteration (all T_con rounds of one iteration share it; node churn
    is an outer-iteration phenomenon, not a per-round one)."""

    def make_sim_mixer(self, W, T_con, *, backend="xla-ref"):
        raise TypeError(f"combine rule {self.name!r} is availability-"
                        f"masked; use make_sim_masked_mixer")

    def make_mesh_mixer(self, axis_name, L, T_con, shifts=(-1, 1),
                        self_weight=None, *, W=None, backend="xla-ref"):
        raise TypeError(f"combine rule {self.name!r} is availability-"
                        f"masked; use make_mesh_masked_mixer")

    def make_virtual_mesh_mixer(self, axis_name, vt, T_con, *,
                                backend="xla-ref"):
        raise TypeError(f"combine rule {self.name!r} is availability-"
                        f"masked; use make_virtual_mesh_masked_mixer")

    def signature(self, T_con: int, **params) -> CommSignature:
        # static pricing cannot see the mask: full-participation worst
        # case (the event-driven clock measures the real cost)
        return CommSignature("gossip", T_con)

    # ---------------------------------------------------- mesh shared

    @staticmethod
    def _mask_keep(m, g, shifts_, L, dtype):
        """Per-device liveness of each shift link: keep_k = m_g ·
        m_{(g+s_k) mod L}."""
        mf = m.astype(dtype)
        return jnp.stack([mf[g] * mf[(g + s) % L] for s in shifts_])

    @classmethod
    def _masked_mesh_round(cls, z, m, axis_name, L, shifts_, weights,
                           backend):
        """One masked gossip round on hardware: the dense
        :meth:`_mesh_round` permutes, but the (K+1,) combine weights are
        re-derived from the mask — dead links zeroed, their weight
        folded into the self weight (the row of
        :func:`masked_mixing_matrix` this device owns).  Full mask:
        ``w·1`` and ``w₀+0`` keep the dense weights bit-for-bit."""
        g = jax.lax.axis_index(axis_name)
        w = jnp.asarray(weights if isinstance(weights, tuple)
                        else weights[g])
        keep = cls._mask_keep(m, g, shifts_, L, w.dtype)
        w_eff = jnp.concatenate([
            (w[0] + jnp.sum(w[1:] * (1.0 - keep)))[None],
            w[1:] * keep])
        nbrs = []
        for s in shifts_:
            perm = [(i, (i - s) % L) for i in range(L)]
            nbrs.append(jax.lax.ppermute(z, axis_name, perm))
        return combine_blocks(z, nbrs, w_eff, backend=backend)


class PartialGossipCombine(MaskedGossipCombine):
    """``partial_gossip`` — per-round participation masking: only links
    whose BOTH endpoints are live carry weight, the dead weight folds
    into the self weight (see :func:`masked_mixing_matrix`), and down
    nodes' rows collapse toward identity (the driver freezes their
    iterate anyway).  With availability ≡ 1 the effective matrix IS W
    bit-for-bit, so trajectories reproduce dense ``dif_altgdmin``
    exactly — the regression anchor of the fault layer."""

    name = "partial_gossip"

    def make_sim_masked_mixer(self, W, T_con: int, *,
                              backend: str = "xla-ref") -> Callable:
        """Simulator closure ``(Z (L, ...), m (L,)) ↦ Z'``.  The masked
        matrix is data-dependent, so fused backends mix round by round
        (no ``W^{T_con}`` hoist); the exact path repeats
        ``stacked_product``'s flattened matmul arithmetic so the full
        mask is bit-identical to dense gossip."""
        from repro.distributed.mixing import SparseWeights
        W = maybe_sparsify(W)
        if T_con == 0:
            return lambda Z, m: Z
        if isinstance(W, SparseWeights):
            return _sparse_masked_gossip_mixer(W, T_con)

        def mix(Z, m):
            Wd = jnp.asarray(W).astype(Z.dtype)
            Weff = masked_mixing_matrix(Wd, m)
            if _fused_wanted(backend, Z.dtype):
                def round_(carry, _):
                    return stacked_dense_mix(carry, Weff,
                                             backend=backend), None
                out, _ = jax.lax.scan(round_, Z, None, length=T_con)
                return out
            flat = Z.reshape(Z.shape[0], -1)

            def round_(carry, _):
                return Weff @ carry, None
            out, _ = jax.lax.scan(round_, flat, None, length=T_con)
            return out.reshape(Z.shape)
        return mix

    def make_mesh_masked_mixer(self, axis_name: str, L: int, T_con: int,
                               shifts: Sequence[int] = (-1, 1),
                               self_weight: float | None = None, *,
                               W=None, backend: str = "xla-ref") -> Callable:
        """Per-device closure ``(z (d, r), m (L,)) ↦ z'`` — the masked
        ppermute round T_con times (the mask rides the scan xs of the
        shared mesh skeleton, replicated on every device)."""
        shifts_, weights = self._mesh_weights(L, shifts, self_weight, W)
        if T_con == 0:
            return lambda z, m: z

        def mix(z, m):
            def round_(carry, _):
                return self._masked_mesh_round(carry, m, axis_name, L,
                                               shifts_, weights,
                                               backend), None
            out, _ = jax.lax.scan(round_, z, None, length=T_con)
            return out
        return mix

    def make_virtual_mesh_masked_mixer(self, axis_name: str, vt,
                                       T_con: int, *,
                                       backend: str = "xla-ref") -> Callable:
        """Per-device virtual-tier closure ``(z (V, d, r), m (L,)) ↦
        z'``: the per-edge masked fold zeroes every edge with a dead
        endpoint and moves the lost mass onto the receiver's diagonal
        (the COO form of :func:`masked_mixing_matrix`'s fold), then runs
        T_con plain segment-sum rounds on the folded slice.  Full mask:
        every keep is 1, the fold is the identity, and the rounds ARE
        the dense virtual lowering's rounds bit-for-bit."""
        if T_con == 0:
            return lambda z, m: z

        def mix(z, m):
            g = jax.lax.axis_index(axis_name)
            arrays = virtual_arrays(vt, z.dtype)
            mf = m.astype(z.dtype).reshape(vt.n_dev, vt.block)
            sel_eff = _virtual_masked_fold(
                vt, _device_slice(arrays, g), g, mf)
            flat = z.reshape(vt.block, -1)

            def round_(carry, _):
                return _virtual_selected_round(carry, vt, axis_name,
                                               sel_eff), None
            out, _ = jax.lax.scan(round_, flat, None, length=T_con)
            return out.reshape(z.shape)
        return mix


class StaleGossipCombine(MaskedGossipCombine):
    """``stale_gossip`` — dropout tolerance on the
    :class:`CompressedGossipCombine` reference-copy machinery: every
    node's PUBLIC COPY x̂ persists across iterations (the state rides
    the drivers' scan carry); a LIVE node re-publishes its iterate each
    round (x̂ ← Z), a DOWN node sends nothing new — its last-delivered
    copy sits in the neighbours' receive queue and mixes in ONCE, in
    the iteration's first AGREE round (the late arrival lands at its
    stale value instead of tearing a hole in the weights).  Rounds
    2..T_con have no fresh packet from a down node to deliver, so the
    down link's weight folds to the receiver's diagonal exactly like
    ``partial_gossip`` — re-mixing the same stale anchor every round
    would compound its weight and halve the contraction rate.  Down
    nodes neither combine (the driver freezes them).  Full mask: every
    copy refreshes to Z, the fold is a no-op, and every round IS dense
    ``W @ Z`` bit-for-bit (the exact-self term never crosses a wire,
    and a live refresh makes the copy exact)."""

    name = "stale_gossip"

    # ------------------------------------------------------- state

    def init_state(self, Z_nodes, **kw):
        """Stacked public copies x̂ (L, d, r), zero — the network starts
        with no beliefs, exactly like the compressed rules (no setup
        exchange)."""
        return jnp.zeros_like(Z_nodes)

    def init_mesh_state(self, z_local, n_shifts: int = 0, **kw):
        """Per-device state: this device's own public copy (1, d, r).
        Unlike the compressed rules no neighbour-copy buffers are
        needed — a round's payload IS the sender's current copy, so
        receivers never hold a fresher belief than what arrives."""
        return jnp.zeros_like(z_local[None])

    # --------------------------------------------------- lowerings

    def make_sim_masked_state_mixer(self, W, T_con: int, *,
                                    backend: str = "xla-ref",
                                    **kw) -> Callable:
        """Simulator closure ``(Z, x̂, m) ↦ (Z', x̂')``."""
        from repro.distributed.mixing import SparseWeights
        W = maybe_sparsify(W)
        if T_con == 0:
            return lambda Z, state, m: (Z, state)
        if isinstance(W, SparseWeights):
            return self._make_sparse_masked_state_mixer(W, T_con)

        def mix(Z, state, m):
            N = Z.shape[0]
            Wd = jnp.asarray(W).astype(Z.dtype)
            Weff = masked_mixing_matrix(Wd, m.astype(Wd.dtype))
            mrow = m.astype(bool)[:, None, None]

            def round_(carry, rd):
                Zc, xhat = carry
                xhat2 = jnp.where(mrow, Zc, xhat)    # live nodes publish
                # the queued stale packet delivers once (round 0, dense
                # W); later rounds fold the dead link to the diagonal
                Wr = jnp.where(rd == 0, Wd, Weff)
                if _fused_wanted(backend, Zc.dtype):
                    Z2 = stacked_dense_mix(xhat2, Wr, backend=backend)
                else:
                    Z2 = (Wr @ xhat2.reshape(N, -1)).reshape(Zc.shape)
                # live g's own copy is exact (x̂₂_g = Z_g), so no self
                # correction is needed; down nodes freeze outright
                Z2 = jnp.where(mrow, Z2, Zc)
                return (Z2, xhat2), None

            (Zf, xf), _ = jax.lax.scan(round_, (Z, state),
                                       jnp.arange(T_con))
            return Zf, xf
        return mix

    @staticmethod
    def _make_sparse_masked_state_mixer(sw, T_con: int):
        """Sparse stale-gossip rounds: round 0 applies the DENSE weights
        to the published copies (the queued stale packet delivers once),
        later rounds the per-edge masked fold — per-round ``where`` on
        the edge values instead of the (L, L) ``jnp.where`` of the dense
        lowering."""
        rows_h, cols_h, vals_h, diag_h = _sparse_arrays(sw)
        L = sw.n

        def mix(Z, state, m):
            rows, cols = jnp.asarray(rows_h), jnp.asarray(cols_h)
            vals = jnp.asarray(vals_h, Z.dtype)
            diag = jnp.asarray(diag_h, Z.dtype)
            vals_eff, diag_eff = _sparse_masked_fold(
                rows, cols, vals, diag, m.astype(Z.dtype), L)
            mrow = m.astype(bool)[:, None, None]

            def round_(carry, rd):
                Zc, xhat = carry
                xhat2 = jnp.where(mrow, Zc, xhat)   # live nodes publish
                vals_rd = jnp.where(rd == 0, vals, vals_eff)
                diag_rd = jnp.where(rd == 0, diag, diag_eff)
                Z2 = sparse_round(xhat2.reshape(L, -1), rows, cols,
                                  vals_rd, diag_rd, L).reshape(Zc.shape)
                Z2 = jnp.where(mrow, Z2, Zc)        # down: freeze
                return (Z2, xhat2), None

            (Zf, xf), _ = jax.lax.scan(round_, (Z, state),
                                       jnp.arange(T_con))
            return Zf, xf
        return mix

    def make_mesh_masked_state_mixer(self, axis_name: str, L: int,
                                     T_con: int,
                                     shifts: Sequence[int] = (-1, 1),
                                     self_weight: float | None = None, *,
                                     W=None, backend: str = "xla-ref",
                                     **kw) -> Callable:
        """Per-device closure ``(z, x̂_own, m) ↦ (z', x̂_own')``: in the
        FIRST round a live device publishes z into its copy, every
        device permutes its copy (a down sender's wire carries its
        queued last-published value), and live devices combine
        self-exact with the K delivered copies under the DENSE weights;
        later rounds have nothing new from down senders, so their link
        weight folds to the receiver's diagonal (``partial_gossip``
        style) instead of re-mixing the same stale packet."""
        shifts_, weights = self._mesh_weights(L, shifts, self_weight, W)
        if T_con == 0:
            return lambda z, state, m: (z, state)

        cls = type(self)

        def mix(z, state, m):
            g = jax.lax.axis_index(axis_name)
            w = (weights if isinstance(weights, tuple) else weights[g])
            w_arr = jnp.asarray(w, dtype=z.dtype)
            keep = cls._mask_keep(m, g, shifts_, L, z.dtype)
            w_fold = jnp.concatenate(
                [(w_arr[0] + jnp.sum(w_arr[1:] * (1.0 - keep)))[None],
                 w_arr[1:] * keep])
            live = m.astype(bool)[g]

            def round_(carry, rd):
                zc, own = carry
                own2 = jnp.where(live, zc[None], own)   # publish if live
                nbrs = []
                for s in shifts_:
                    perm = [(i, (i - s) % L) for i in range(L)]
                    nbrs.append(jax.lax.ppermute(own2, axis_name, perm))
                # queued stale packet mixes once (round 0, dense w);
                # afterwards the dead link's weight folds to self
                w_rd = jnp.where(rd == 0, w_arr, w_fold)
                z2 = combine_blocks(zc, [n[0] for n in nbrs], w_rd,
                                    backend=backend)
                z2 = jnp.where(live, z2, zc)            # down: freeze
                return (z2, own2), None

            (zf, of), _ = jax.lax.scan(round_, (z, state),
                                       jnp.arange(T_con))
            return zf, of
        return mix

    def make_virtual_mesh_masked_state_mixer(self, axis_name: str, vt,
                                             T_con: int, *,
                                             backend: str = "xla-ref",
                                             **kw) -> Callable:
        """Per-device virtual-tier closure ``(z (V, d, r), x̂ (V, d, r),
        m (L,)) ↦ (z', x̂')`` — the simulator's sparse stale rounds on
        the device's block slice: live virtual nodes publish into their
        copies, round 0 mixes the published copies under the UNMASKED
        edge values (the queued stale packet delivers once), later
        rounds under the per-edge masked fold; down nodes freeze."""
        if T_con == 0:
            return lambda z, state, m: (z, state)

        def mix(z, state, m):
            V = vt.block
            g = jax.lax.axis_index(axis_name)
            arrays = virtual_arrays(vt, z.dtype)
            sel = _device_slice(arrays, g)
            mf = m.astype(z.dtype).reshape(vt.n_dev, V)
            sel_m = _virtual_masked_fold(vt, sel, g, mf)
            lr, lc, lv, crs, ccs, cvs, dg = sel
            _, _, lv_m, _, _, cvs_m, dg_m = sel_m
            mrow = m.astype(bool).reshape(vt.n_dev, V)[g][:, None, None]

            def round_(carry, rd):
                zc, xhat = carry
                xhat2 = jnp.where(mrow, zc, xhat)   # live nodes publish
                sel_rd = (lr, lc, jnp.where(rd == 0, lv, lv_m),
                          crs, ccs,
                          [jnp.where(rd == 0, a, b)
                           for a, b in zip(cvs, cvs_m)],
                          jnp.where(rd == 0, dg, dg_m))
                acc = _virtual_selected_round(xhat2.reshape(V, -1), vt,
                                              axis_name, sel_rd)
                Z2 = jnp.where(mrow, acc.reshape(zc.shape), zc)
                return (Z2, xhat2), None

            (zf, xf), _ = jax.lax.scan(round_, (z, state),
                                       jnp.arange(T_con))
            return zf, xf
        return mix


class PushSumGossipCombine(MaskedGossipCombine):
    """``push_sum_gossip`` — ratio-consensus for the DIRECTED mixing
    matrices dropout induces: the masked matrix
    (:func:`push_sum_matrix`) is only column-stochastic, so plain
    gossip would drift toward a weighted (biased) average; push-sum
    carries a companion weight scalar w through the same matrix
    (z ← Cz, w ← Cw, w₀ = 1) and reads out the bias-corrected ratio
    z/w after the T_con rounds.  The weight vector stays a probability
    vector up to scale (Σ_g w_g = L — columns sum to one), the
    invariant the tests pin.  The weight resets to 1 each outer
    iteration (each AGREE phase is its own push-sum episode), so no
    cross-iteration state is carried.  Full mask on a doubly stochastic
    W: C ≈ W and w ≈ 1 up to the row sums' float round-off — the
    degenerate case matches dense gossip to machine precision (not
    bit-for-bit: the ratio correction is genuinely different
    arithmetic)."""

    name = "push_sum_gossip"

    def make_sim_masked_mixer(self, W, T_con: int, *,
                              backend: str = "xla-ref") -> Callable:
        from repro.distributed.mixing import SparseWeights
        W = maybe_sparsify(W)
        if T_con == 0:
            return lambda Z, m: Z
        if isinstance(W, SparseWeights):
            return self._make_sparse_masked_mixer(W, T_con)

        def mix(Z, m):
            N = Z.shape[0]
            Wd = jnp.asarray(W).astype(Z.dtype)
            C = push_sum_matrix(Wd, m)
            flat = Z.reshape(N, -1)
            w0 = jnp.ones((N, 1), Z.dtype)

            def round_(carry, _):
                zf, wv = carry
                if _fused_wanted(backend, Z.dtype):
                    zf = stacked_dense_mix(zf, C, backend=backend)
                    wv = stacked_dense_mix(wv, C, backend=backend)
                else:
                    zf, wv = C @ zf, C @ wv
                return (zf, wv), None

            (zf, wv), _ = jax.lax.scan(round_, (flat, w0), None,
                                       length=T_con)
            out = zf / jnp.where(wv > 0, wv, 1.0)    # bias correction
            return out.reshape(Z.shape)
        return mix

    @staticmethod
    def _make_sparse_masked_mixer(sw, T_con: int):
        """Sparse push-sum: the column normalizer is a segment-sum over
        SENDER columns of the masked edge values (``c_j = W_jj +
        Σ_{i≠j} m_i m_j W_ij`` — the self link always stays, exactly
        like :func:`push_sum_matrix`), the column-stochastic edge
        values are ``vals_m / c[col]``, and the companion weight vector
        rides the same rounds."""
        rows_h, cols_h, vals_h, diag_h = _sparse_arrays(sw)
        L = sw.n

        def mix(Z, m):
            rows, cols = jnp.asarray(rows_h), jnp.asarray(cols_h)
            vals = jnp.asarray(vals_h, Z.dtype)
            diag = jnp.asarray(diag_h, Z.dtype)
            mf = m.astype(Z.dtype)
            vals_m = vals * mf[rows] * mf[cols]
            # live column mass: padding cols point at 0 but carry
            # weight 0, so the unsorted sender-side segment_sum is safe
            c = diag + jax.ops.segment_sum(vals_m, cols, num_segments=L)
            c = jnp.where(c > 0, c, 1.0)
            vals_C = vals_m / c[cols]
            diag_C = diag / c
            flat = Z.reshape(L, -1)
            w0 = jnp.ones((L, 1), Z.dtype)

            def round_(carry, _):
                zf, wv = carry
                zf = sparse_round(zf, rows, cols, vals_C, diag_C, L)
                wv = sparse_round(wv, rows, cols, vals_C, diag_C, L)
                return (zf, wv), None

            (zf, wv), _ = jax.lax.scan(round_, (flat, w0), None,
                                       length=T_con)
            out = zf / jnp.where(wv > 0, wv, 1.0)    # bias correction
            return out.reshape(Z.shape)
        return mix

    def make_mesh_masked_mixer(self, axis_name: str, L: int, T_con: int,
                               shifts: Sequence[int] = (-1, 1),
                               self_weight: float | None = None, *,
                               W=None, backend: str = "xla-ref") -> Callable:
        """Per-device push-sum round: the sender normalizes its OWN
        column locally (w_eff over its live links — exact because W must
        be symmetric, validated below, so its row IS its column),
        pre-scales the payload (z/c, w/c), and receivers combine with
        their masked row weights.  Requires a symmetric mixing matrix;
        asymmetric topologies need a sender-side column exchange the
        mesh lowering does not implement."""
        if W is not None:
            Wn = np.asarray(W)
            if not np.allclose(Wn, Wn.T):
                raise ValueError(
                    "push_sum_gossip's mesh lowering computes each "
                    "sender's column normalizer from its own row, which "
                    "requires a symmetric mixing matrix")
        elif set(shifts) != {-s for s in shifts}:
            raise ValueError(
                f"push_sum_gossip's mesh lowering needs symmetric "
                f"circulant shifts (closed under negation), got "
                f"{tuple(shifts)}")
        shifts_, weights = self._mesh_weights(L, shifts, self_weight, W)
        if T_con == 0:
            return lambda z, m: z

        def mix(z, m):
            g = jax.lax.axis_index(axis_name)
            w = jnp.asarray(weights if isinstance(weights, tuple)
                            else weights[g])
            keep = self._mask_keep(m, g, shifts_, L, w.dtype)
            # own column's live mass (symmetric W: row slice = column)
            c = w[0] + jnp.sum(w[1:] * keep)
            c = jnp.where(c > 0, c, 1.0)
            w_eff = jnp.concatenate([w[:1], w[1:] * keep])
            # the companion weight varies per device once the rounds run
            wv0 = jax.lax.pcast(jnp.ones((), z.dtype), axis_name,
                                to="varying")

            def round_(carry, _):
                zc, wv = carry
                zs = zc / c.astype(zc.dtype)         # pre-scaled payload
                ws = wv / c.astype(zc.dtype)
                nbrs_z, nbrs_w = [], []
                for s in shifts_:
                    perm = [(i, (i - s) % L) for i in range(L)]
                    nbrs_z.append(jax.lax.ppermute(zs, axis_name, perm))
                    nbrs_w.append(jax.lax.ppermute(ws, axis_name, perm))
                z2 = combine_blocks(zs, nbrs_z, w_eff, backend=backend)
                acc_dt = _acc_dtype(zc.dtype)
                w2 = w_eff.astype(acc_dt)[0] * ws.astype(acc_dt)
                for k, nw in enumerate(nbrs_w):
                    w2 = w2 + w_eff.astype(acc_dt)[k + 1] \
                        * nw.astype(acc_dt)
                return (z2, w2.astype(zc.dtype)), None

            (zf, wv), _ = jax.lax.scan(round_, (z, wv0), None,
                                       length=T_con)
            return zf / jnp.where(wv > 0, wv, 1.0)
        return mix

    def make_virtual_mesh_masked_mixer(self, axis_name: str, vt,
                                       T_con: int, *,
                                       backend: str = "xla-ref") -> Callable:
        """Per-device virtual-tier push-sum ``(z (V, d, r), m (L,)) ↦
        z'``: each virtual node's column normalizer is its own RECEIVER-
        side live mass (row slice = column slice under the symmetry
        requirement, checked at make time), payloads are pre-scaled
        (z/c, w/c) and pushed through the MASKED edge values with the
        ORIGINAL diagonal — arithmetic-identical to the simulator's
        column-stochastic ``vals_m / c[col]`` rounds because
        ``vals_C·z[col] = vals_m·(z/c)[col]`` and ``diag_C·z =
        diag·(z/c)`` — with the companion weight riding the same
        rounds."""
        if not _vt_is_symmetric(vt):
            raise ValueError(
                "push_sum_gossip's virtual-mesh lowering computes each "
                "sender's column normalizer from its own receiver-side "
                "mass, which requires a symmetric mixing matrix")
        if T_con == 0:
            return lambda z, m: z

        def mix(z, m):
            V = vt.block
            g = jax.lax.axis_index(axis_name)
            arrays = virtual_arrays(vt, z.dtype)
            sel = _device_slice(arrays, g)
            mf = m.astype(z.dtype).reshape(vt.n_dev, V)
            # masked edges, ORIGINAL diagonal: the self link always
            # stays live, exactly like push_sum_matrix
            sel_m = _virtual_masked_fold(vt, sel, g, mf, fold_diag=False)
            lr, _, lv_m, crs, _, cvs_m, dg = sel_m
            # own column's live mass, receiver side (symmetric W)
            c = dg + jax.ops.segment_sum(lv_m, lr, num_segments=V + 1,
                                         indices_are_sorted=True)[:V]
            for k in range(len(vt.dev_shifts)):
                c = c + jax.ops.segment_sum(cvs_m[k], crs[k],
                                            num_segments=V + 1,
                                            indices_are_sorted=True)[:V]
            c = jnp.where(c > 0, c, 1.0)
            flat = z.reshape(V, -1)
            w0 = jax.lax.pcast(jnp.ones((V, 1), z.dtype), axis_name,
                               to="varying")

            def round_(carry, _):
                zf, wv = carry
                zs = zf / c[:, None]                 # pre-scaled payload
                ws = wv / c[:, None]
                zf2 = _virtual_selected_round(zs, vt, axis_name, sel_m)
                wv2 = _virtual_selected_round(ws, vt, axis_name, sel_m)
                return (zf2, wv2), None

            (zf, wv), _ = jax.lax.scan(round_, (flat, w0), None,
                                       length=T_con)
            out = zf / jnp.where(wv > 0, wv, 1.0)    # bias correction
            return out.reshape(z.shape)
        return mix


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------

COMBINE_RULES: dict[str, CombineRule] = {}


def register_rule(rule: CombineRule) -> CombineRule:
    if rule.name in COMBINE_RULES:
        raise ValueError(f"combine rule {rule.name!r} already registered")
    COMBINE_RULES[rule.name] = rule
    return rule


def get_rule(name: str) -> CombineRule:
    try:
        return COMBINE_RULES[name]
    except KeyError:
        raise ValueError(f"unknown combine rule {name!r}; registered: "
                         f"{sorted(COMBINE_RULES)}") from None


def rule_names() -> tuple[str, ...]:
    return tuple(sorted(COMBINE_RULES))


for _rule in (GossipCombine(), NeighborCombine(), CentralCombine(),
              NoCombine(), ExactDiffusionCombine(), BeyondCentralCombine(),
              TopkGossipCombine(), QuantizedGossipCombine(),
              EventGossipCombine(), PartialGossipCombine(),
              StaleGossipCombine(), PushSumGossipCombine()):
    register_rule(_rule)
