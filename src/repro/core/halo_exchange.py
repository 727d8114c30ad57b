"""HaloExchange — DIGEST's stale-representation KVS, owner-sharded and
precision-aware.

This subsystem implements the PUSH/PULL lines of Algorithm 1 over a
**compact, owner-sharded** slab that holds only *boundary* nodes — rows
that appear in at least one subgraph's halo — instead of the dense
``(L-1, N+1, hidden)`` array the seed used.

Owner-sharded layout (see ``repro.graph.partition.build_partitions``):
the slot space is M contiguous shards of ``shard_rows`` rows, shard m
holding exactly the boundary rows *owned* (pushed) by part m, with the
last row of every shard a per-owner zero sentinel.  Sharded slot-wise
over the mesh "data" axis, device m therefore stores ``1/M`` of the slab
and every PUSH scatter is shard-local.  Mapping to the paper:

  * Algorithm 1 line 9–10 (``PUSH h_v^(ℓ) for v ∈ V_m``)  →  :func:`push`
    (SPMD scatter; the partitioner routes every row of part m into shard
    m, so writes never cross devices) or :func:`shard_push` (the explicit
    ``shard_map`` form with owner-local offsets).  Non-boundary local
    rows are dropped via the owner's sentinel row — no other subgraph
    ever reads them (this is what shrinks the store from O(N·L·d) to
    O(|boundary|·L·d), the Fig. 9 memory term).
  * Algorithm 1 line 5 (``PULL h̃_u^(ℓ) for u ∈ halo(G_m)``)  →
    :func:`pull_slab` (dense-gather form: under pjit XLA lowers it to an
    all-gather of the shards — the fallback) or :func:`collective_pull`
    (the ragged ``shard_map`` form: an ``all_to_all`` that ships only the
    slots each subgraph's halo actually references, per the
    :class:`~repro.graph.partition.PullPlan`).  Both return a
    **device-local** per-subgraph slab ``(M, L-1, H+1, hidden)`` in
    storage precision — non-pull epochs read this local slice through the
    fused pull+aggregate kernel :func:`repro.kernels.spmm.halo_spmm`, so
    nothing replicated and no ``(M, L-1, H, hidden)`` fp32 cache is ever
    materialized.
  * §3.3 communication terms  →  :meth:`HaloSpec.comm_bytes`: the ragged
    pull ships ``Σ_m |halo(G_m)| · (L-1) · row_bytes`` per sync versus
    ``(M-1) · store_nbytes`` for the replicated snapshot
    (:meth:`HaloSpec.replicated_pull_nbytes`); pushes ship
    ``Σ_m |boundary ∩ V_m| · (L-1) · row_bytes``.
  * Theorem 1's per-layer staleness ε^(ℓ)  →  :func:`staleness_error`,
    measured over the rows actually served to other subgraphs.

Precision (:class:`HaloPrecision`) is pluggable and applies to both the
slab layout (storage) and the §3.3 wire format:

  ======  ==================================  ==========================
  mode    row encoding                        bytes / hidden value
  ======  ==================================  ==========================
  fp32    float32                             4
  bf16    bfloat16                            2
  int8    int8 + one float32 scale per row    1 (+ 4 / hidden amortized)
  ======  ==================================  ==========================

int8 uses symmetric per-row quantization: ``scale = max|row| / 127``,
``q = round(row / scale)``; the absolute dequantization error is bounded
by ``scale / 2 = max|row| / 254`` per element.  With
``HaloPrecision(error_feedback=True)`` the pusher accumulates the per-row
rounding residual (:func:`push_ef`), so repeated pushes of slowly-moving
representations stay unbiased at the same wire cost (Bai et al. 2023).

Second role: control-variate history for sampled training
----------------------------------------------------------

The same store serves the mini-batch regime
(:func:`repro.core.digest.make_sampled_epoch_fn`) as VR-GCN-style
**variance-reduction history** (arXiv 1710.10568): a sampled step
aggregates its fanout-bounded in-batch neighbors *fresh* and lets the
out-of-batch complement read *historical* activations, so the estimate
is ``agg(hist, all nbrs) + agg(scale·(fresh − hist), sampled)`` — the
history term is a control variate, not a dropped edge.  Store contract
per sampled step:

  * **Reads.**  Out-of-subgraph (halo) neighbors read the pulled slab —
    the SAME per-subgraph cache, refreshed by the unchanged PULL at the
    ``sync_interval`` cadence, in storage precision through the same
    ``halo_spmm`` path.  In-subgraph out-of-batch neighbors read the
    device-local fp32 history ``state["hist"]`` (each part's own rows
    from the previous step — never exchanged, never quantized).
  * **Writes.**  The step computes every local row's representation
    anyway (padded SPMD), so it refreshes ``state["hist"]`` wholesale
    every step and runs the unchanged PUSH (boundary rows into the
    owner shard) on the Algorithm-1 schedule.
  * **Communication.**  Byte-identical to the full-batch epoch — the
    pull/push helpers are shared, so the compiled census (zero
    all-gathers, one ragged all_to_all per store tensor) is a pinned
    regression property (tests/test_sampling.py).

``sync_interval`` therefore controls ONLY the halo side's staleness:
local history is at most one step stale, halo history up to
``sync_interval`` steps — exactly the Theorem-1 ε tradeoff, now also
dialing the control variate's residual variance.  When ``fanout >= max
in-degree`` the residual weights are exactly +0.0 and the estimator
collapses bitwise to the full-batch aggregation, whatever the store or
history holds.

Occupancy worklist (the chunk-skipping streamed read path)
----------------------------------------------------------

Non-pull epochs read the pulled per-subgraph slabs through the streamed
``halo_spmm`` kernels, whose DMA schedule can consult a **static
(row-block × chunk) worklist** computed once at partition time
(:func:`repro.graph.partition.build_chunk_worklist` /
``StackedPartitions.chunk_worklist``).  Format — CSR padded to a static
width so it jits as two dense int32 arrays riding in the struct dict
next to the out-ELL they were computed from:

  ``wl_ids`` (M, n_row_blocks, max_chunks_per_block)
      ascending slab-chunk ids row block i of subgraph m must visit;
      entries past the valid prefix REPEAT the last visited chunk (0 for
      empty blocks) so padded grid steps re-address the chunk already in
      VMEM instead of DMA-ing a new one.
  ``wl_cnt`` (M, n_row_blocks)
      valid prefix length; the kernel masks grid steps ``t >= cnt`` out
      of the accumulation, which keeps the skip stream **bitwise equal**
      to the dense stream (skipped chunks contribute exact ±0.0 terms).

Geometry is bound to the kernel tiling: 128-row output blocks
(``kernels.spmm.BLOCK_ROWS``) over the padded S rows, ``chunk_rows``-row
chunks over the (H+1)-row slab — rebuild the worklist when either
changes.  The owner-sharded slot layout is what makes this pay: each
subgraph's halo references cluster in a few owner shards, so measured
occupancy (``ChunkWorklist.occupancy``, the static kernel-selection
signal threaded through ``GNNConfig.halo_occupancy``) sits far below 1
and streamed bytes scale with occupied work, not slab size.

Slab layout under ``build_partitions(order=...)``: every slab is laid
out as contiguous owner runs (the slab-side mirror of the owner-sharded
store), but the row order *within* each owner run is the partitioner's
choice — ascending global id at ``order="none"``, first-referencing
local row at ``order="rcm"`` (so an RCM-ordered row block's references
land in adjacent slab chunks).  Nothing in this module depends on the
within-run order: the :class:`PullPlan` send offsets / recv positions,
``halo_slots`` and the worklist are all derived from the same
``halo_ids`` table after the re-lay, pushes scatter by owner-local slot
(store layout is order-independent), and the per-row ELL edge order is
untouched — so pulled rows, pushed stores and aggregation outputs are
bitwise identical across orders (tests/test_order_invariance.py).

Multi-pod two-stage routing (the ("pod", "data") mesh)
------------------------------------------------------

The collective paths auto-detect the mesh shape
(:func:`exchange_axes`): on a single-pod mesh M is sharded over the
"data" axis alone and a pull is one ragged ``all_to_all``; on the
production multi-pod mesh (axes ``("pod", "data", "model")``) M is
sharded over the **combined** ``("pod", "data")`` axes — device
``(p, d)`` owns the ``k = M/(pods·data)`` shards of combined block
``e = p·data + d`` — and the exchange runs in **two stages**, mirroring
how DistDGL-style systems split inter-machine from intra-machine
traffic:

  1. *intra-pod*: one ragged ``all_to_all`` over "data", routing every
     (owner, requester) block by the requester's **data coordinate**
     d_r within the owner's pod — after this hop, device ``(p, d)``
     holds every block its pod owns that is destined for data-column d
     of *any* pod;
  2. *inter-pod*: ``pods − 1`` shifted ``ppermute`` rounds over "pod"
     (a **single collective-permute per store tensor** on the 2-pod
     production mesh), routing by the requester's pod coordinate p_r —
     only this stage rides the slow inter-pod links, and it ships each
     row exactly once.

No routing table changes: the :class:`~repro.graph.partition.PullPlan`
is the same (M_owner, M_req, K) pair of tables — send offsets owner-
local, recv positions requester-local — and the two-stage kernel merely
*re-blocks* the requester axis as ``(d_r, p_r, b)`` for stage 1 and
``(p_r, d_o, b)`` for stage 2 (b the requester-local shard index,
d_o the owner's data coordinate).  Flattening the owner axis back as
``(p_o, d_o, a)`` reproduces the exact single-axis ordering, which is
why multi-pod pulls/pushes are **bitwise equal** to the single-pod
collective and the dense-gather fallback (gathers, transposes and
scatters only — regression-pinned in tests/test_multipod.py).  Pushes
and the Theorem-1 staleness probe stay owner-local on any mesh shape:
they only need the combined block index ``e``, never a collective.

A store is a plain pytree (dict) so it drops into jitted state, pjit
shardings and npz checkpoints unchanged:

    {"data": (L-1, R, hidden) <storage dtype>}        fp32 / bf16
    {"data": int8 ..., "scale": (L-1, R, 1) float32}  int8

where ``R = M · shard_rows``.  Sentinel rows (one per shard; the global
sentinel is the last row of the last shard) are re-zeroed after every
push, so pulls of padded halo slots are exactly zero.

Read-path / refresh contract (serving)
--------------------------------------

``repro.core.serving`` builds an online query engine on this module, so
the store API doubles as a serving contract:

  * **Reads are layout-pure.**  :func:`collective_pull` /
    :func:`pull_slab` / :func:`layer_table` depend only on the pytree
    shapes above — any leading layer count works (serving uses a
    single-layer all-node slab whose ``shard_rows`` is the padded part
    size + 1), and ``owner = slot // shard_rows`` is the one invariant
    routing relies on.  Extra pytree keys (serving adds an int32
    ``"version"`` scalar) must be stripped before calling in
    (``serving.store_bare``): the exchange paths iterate exactly
    {"data"[, "scale"]}, and :func:`precision_of` keys off ``"scale"``.
  * **Writes go through push, and every refresh is a version bump.**
    :func:`push` / :func:`shard_push` are total-row overwrites of the
    pushed slots (quantize + scatter + sentinel re-zero) — there is no
    partial-row state, so a reader that observed slot s either sees the
    old row or the new row, never a blend.  Serving relies on this plus
    one rule of its own: any refresh that could change a served value
    (new representations OR new top-layer weights) must bump the store
    version, because the hot-row cache invalidates by version equality,
    never by scanning rows.
  * **Donation is safe.**  Push scatters are in-place updates of the
    store operand, so jitting a refresh with ``donate_argnums`` on the
    store reuses its buffers — a serving deployment holds one
    store-sized allocation across refreshes (``serving.make_refresh_fn``).
  * **Reads degrade, never fail.**  A missed push (dropped, corrupted-
    and-rejected, or a crashed owner) leaves last-known-good rows in
    place — see the degraded-pull contract on :func:`pull`; serving's
    analogue is ``serving.refresh_or_degrade`` (a failed refresh keeps
    the old version serving, cache intact, counted in
    ``degraded_refreshes``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PRECISIONS = ("fp32", "bf16", "int8")

_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}
_VALUE_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}


@dataclasses.dataclass(frozen=True)
class HaloPrecision:
    """Wire/storage precision of the halo slab (one knob for both)."""

    storage: str = "fp32"          # fp32 | bf16 | int8
    # Accumulate the per-row quantization residual at the pusher
    # (push_ef) so repeated pushes stay unbiased.  Only meaningful for
    # lossy storage (int8 / bf16); a no-op for fp32.
    error_feedback: bool = False

    def __post_init__(self):
        if self.storage not in PRECISIONS:
            raise ValueError(f"storage {self.storage!r} not in {PRECISIONS}")

    @property
    def dtype(self):
        return _DTYPES[self.storage]

    @property
    def has_scale(self) -> bool:
        return self.storage == "int8"

    def row_bytes(self, hidden: int) -> int:
        """Bytes to store/ship one node-layer row of width ``hidden``."""
        extra = 4 if self.has_scale else 0       # one fp32 scale per row
        return hidden * _VALUE_BYTES[self.storage] + extra


@dataclasses.dataclass(frozen=True)
class HaloSpec:
    """Static shape/precision metadata of a compact store (accounting)."""

    num_hidden_layers: int          # L-1
    num_slots: int                  # |boundary| (excl. sentinels/padding)
    hidden: int
    precision: HaloPrecision = HaloPrecision()
    # Owner-sharded layout: R = store_rows slab rows over num_shards
    # devices.  Defaults describe the unsharded (single-sentinel) layout.
    store_rows: Optional[int] = None
    num_shards: int = 1

    @classmethod
    def from_partitions(cls, sp, hidden: int, num_layers: int,
                        precision: HaloPrecision = HaloPrecision()
                        ) -> "HaloSpec":
        return cls(num_hidden_layers=max(num_layers - 1, 1),
                   num_slots=sp.num_boundary, hidden=hidden,
                   precision=precision, store_rows=sp.store_rows,
                   num_shards=sp.num_parts)

    def init(self) -> dict:
        rows = (self.store_rows if self.store_rows is not None
                else self.num_slots + 1)
        return init_store(self.num_hidden_layers, rows - 1,
                          self.hidden, self.precision)

    # -- §3.3 / Fig. 9 accounting ------------------------------------------
    def store_nbytes(self) -> int:
        """Total HBM bytes of the slab (incl. sentinel/padding rows)."""
        rows = (self.store_rows if self.store_rows is not None
                else self.num_slots + 1)
        return (self.num_hidden_layers * rows
                * self.precision.row_bytes(self.hidden))

    def shard_nbytes(self) -> int:
        """Per-device resident bytes under the owner-sharded layout."""
        return self.store_nbytes() // self.num_shards

    def dense_nbytes(self, num_nodes: int) -> int:
        """What the seed's dense fp32 ``(L-1, N+1, hidden)`` store costs."""
        return self.num_hidden_layers * (num_nodes + 1) * self.hidden * 4

    def replicated_pull_nbytes(self) -> int:
        """Wire bytes per sync to replicate the compact slab on every
        device — the PR-1 snapshot layout's all-gather: each of the M
        devices receives the other M-1 shards of the *unpadded*
        (|boundary|+1)-row slab (per-owner shard padding is a storage
        artifact of this layout, not bytes the replicated baseline
        shipped)."""
        return ((self.num_shards - 1) * self.num_hidden_layers
                * (self.num_slots + 1)
                * self.precision.row_bytes(self.hidden))

    def comm_bytes(self, pull_rows: int, push_rows: int) -> dict:
        """Per-sync §3.3 byte counts under the configured wire precision.

        pull_rows: Σ_m |halo(G_m)| — rows gathered by all subgraphs (the
          *information-theoretic* pull cost; the implemented dense
          all_to_all pads per-pair lists to a common width — see
          :meth:`collective_pull_nbytes` for what actually hits the wire).
        push_rows: Σ_m |boundary ∩ V_m| — rows scattered by all subgraphs.
        """
        rb = self.precision.row_bytes(self.hidden)
        pull = int(pull_rows) * self.num_hidden_layers * rb
        push = int(push_rows) * self.num_hidden_layers * rb
        return {"pull_bytes": pull, "push_bytes": push,
                "total_bytes": pull + push}

    def collective_pull_nbytes(self, plan_max_rows: int) -> int:
        """Actual wire bytes of one :func:`collective_pull` sync: the
        all_to_all pads every (owner, requester) pair to the plan's max
        width K, shipping M·M·K rows.  Close to the ragged ideal
        (``comm_bytes``'s pull term) for balanced partitions; a skewed
        pair inflates it — compare both before choosing pull_mode."""
        return (self.num_shards * self.num_shards * int(plan_max_rows)
                * self.num_hidden_layers
                * self.precision.row_bytes(self.hidden))


def precision_of(store: dict) -> HaloPrecision:
    if "scale" in store:
        return HaloPrecision("int8")
    if store["data"].dtype == jnp.bfloat16:
        return HaloPrecision("bf16")
    return HaloPrecision("fp32")


# ---------------------------------------------------------------------------
# Quantization
# ---------------------------------------------------------------------------

def quantize_rows(x: jax.Array, precision: HaloPrecision
                  ) -> tuple[jax.Array, Optional[jax.Array]]:
    """Encode fp32 rows (..., hidden) into (data, scale-or-None)."""
    if precision.storage == "fp32":
        return x.astype(jnp.float32), None
    if precision.storage == "bf16":
        return x.astype(jnp.bfloat16), None
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_rows(data: jax.Array, scale: Optional[jax.Array]
                    ) -> jax.Array:
    out = data.astype(jnp.float32)
    return out if scale is None else out * scale


# ---------------------------------------------------------------------------
# The KVS operations (compact-slot indexed)
# ---------------------------------------------------------------------------

def init_store(num_hidden_layers: int, num_slots: int, hidden: int,
               precision: HaloPrecision = HaloPrecision()) -> dict:
    """Zero slab; (L-1, num_slots+1, hidden).  For the owner-sharded
    layout pass ``num_slots = store_rows - 1`` (sentinel rows included)."""
    store = {"data": jnp.zeros((num_hidden_layers, num_slots + 1, hidden),
                               precision.dtype)}
    if precision.has_scale:
        store["scale"] = jnp.ones((num_hidden_layers, num_slots + 1, 1),
                                  jnp.float32)
    return store


def init_slab(num_parts: int, num_hidden_layers: int, halo_size: int,
              hidden: int, precision: HaloPrecision = HaloPrecision()
              ) -> dict:
    """Zero per-subgraph halo slab — the device-local pull target:
    {"data": (M, L-1, H+1, hidden)} with the zero sentinel row at H."""
    slab = {"data": jnp.zeros(
        (num_parts, num_hidden_layers, halo_size + 1, hidden),
        precision.dtype)}
    if precision.has_scale:
        slab["scale"] = jnp.ones(
            (num_parts, num_hidden_layers, halo_size + 1, 1), jnp.float32)
    return slab


def layer_table(store: dict, ell: int
                ) -> tuple[jax.Array, Optional[jax.Array]]:
    """(data, scale) slab of hidden layer ``ell`` — feeds the fused kernel.

    Works on both the full store (L-1, R, hidden) and one subgraph's
    pulled slab (L-1, H+1, hidden)."""
    return store["data"][ell], (store["scale"][ell] if "scale" in store
                                else None)


def pull(store: dict, slots: jax.Array) -> jax.Array:
    """Gather + dequantize stale halo tables (Algorithm 1 line 5).

    slots: (M, H) compact slot ids (sentinel rows at padding).
    Returns (M, L-1, H, hidden) float32.

    Degraded-pull contract (fault tolerance): a pull NEVER fails — it
    returns whatever rows the store currently holds.  Because pushes
    are total-row overwrites and a dropped/rejected/crashed push simply
    writes nothing (masked rows route to the owner's sentinel slot),
    the rows a faulted owner failed to refresh are its
    *last-known-good* representations, not zeros or torn blends.  Under
    the paper's Theorems 1/3 that degradation is just additional
    staleness; the engines keep it measured (never silent) through the
    per-slot/per-shard ``last_push_*`` age tables
    (:mod:`repro.core.faults`) and bound it with the ``max_staleness``
    watchdog's forced resync.
    """
    out = store["data"][:, slots, :].astype(jnp.float32)   # (L-1, M, H, h)
    if "scale" in store:
        out = out * store["scale"][:, slots, :]
    return jnp.swapaxes(out, 0, 1)


def pull_slab(store: dict, halo_slots: jax.Array) -> dict:
    """Collective PULL, dense-gather form (Algorithm 1 line 5).

    Gathers each subgraph's halo rows into a **device-local** slab in
    storage precision: {"data": (M, L-1, H+1, hidden)[, "scale"]}, slab
    row H the zero sentinel (``out_nbr`` padding).  Under pjit with the
    store sharded slot-wise and the result sharded over "data", XLA
    lowers the gather to an all-gather of the shards — the dense fallback
    of :func:`collective_pull`; on one device it is a plain gather.
    """
    data = jnp.swapaxes(store["data"][:, halo_slots, :], 0, 1)
    out = {"data": jnp.pad(data, ((0, 0), (0, 0), (0, 1), (0, 0)))}
    if "scale" in store:
        sc = jnp.swapaxes(store["scale"][:, halo_slots, :], 0, 1)
        out["scale"] = jnp.pad(sc, ((0, 0), (0, 0), (0, 1), (0, 0)),
                               constant_values=1.0)
    return out


def exchange_axes(mesh, axis: str = "data") -> tuple:
    """Mesh axes the halo exchange shards M over — the auto-detection
    behind ``pull_mode="collective"``.

    Single-pod meshes exchange over ``(axis,)``; a mesh carrying a
    "pod" axis exchanges over the combined ``("pod", axis)`` — device
    ``(p, d)`` then owns combined block ``e = p·mesh[axis] + d`` and
    pulls run the two-stage intra-pod/inter-pod exchange (see the
    module docstring's routing-table section).
    """
    return ("pod", axis) if "pod" in mesh.axis_names else (axis,)


def exchange_size(mesh, axis: str = "data") -> int:
    """Total devices along the exchange axes (pods · data)."""
    num = 1
    for a in exchange_axes(mesh, axis):
        num *= int(mesh.shape[a])
    return num


def _combined_index(mesh, axis: str = "data"):
    """Traced combined block index e = p·data + d of the calling device
    (inside ``shard_map``); plain data index on single-pod meshes."""
    e = jax.lax.axis_index(axis)
    if "pod" in mesh.axis_names:
        e = e + jax.lax.axis_index("pod") * int(mesh.shape[axis])
    return e


def shards_per_device(num_parts: int, mesh, axis: str = "data",
                      what: str = "collective halo exchange") -> int:
    """k = num_parts / (pods · mesh[axis]) — owner shards per device.

    Mesh-facing form of the single authoritative divisibility check,
    :func:`repro.graph.partition.parts_per_device` (see there for why a
    non-multiple M must be rejected loudly).  Counts every exchange
    axis, so the multi-pod mesh needs M to be a multiple of pods·data.
    """
    from repro.graph.partition import parts_per_device

    return parts_per_device(num_parts, exchange_size(mesh, axis), what)


# A program spanning several devices may hold a Pallas kernel only inside
# a shard_map whose every mesh axis is manual: XLA cannot partition the
# kernel's custom call ("Mosaic kernels cannot be automatically
# partitioned").  Every multi-device path that aggregates goes through one
# of the two wrappers below.

def per_subgraph(fn, mesh, in_axes: tuple, what: str = "per-subgraph step",
                 check_vma: bool = True):
    """``vmap(fn)`` over the stacked subgraph axis M — Algorithm 1's
    ``for m in parallel``; ``in_axes`` entries are 0 (M-leading) or None
    (replicated, e.g. the parameters).

    With a mesh the vmap runs inside a ``shard_map`` over the exchange
    axes, so every device computes its own k = M/devices subgraphs from
    device-local operands; nothing in ``fn`` may cross subgraphs.  Pass
    ``check_vma=repro.kernels.spmm.vma_checkable(backend)``."""
    batched = jax.vmap(fn, in_axes=in_axes)
    if mesh is None:
        return batched
    axes = exchange_axes(mesh)
    local = P(axes)

    def body(*args):
        # Replicated operands enter as per-device copies, so a gradient
        # taken inside ``fn`` is this device's own.  Left invariant, the
        # varying-axes check would transpose their implicit broadcast
        # into a sum of the gradients over devices.
        return batched(*(a if ax == 0 else jax.tree.map(
            lambda x: jax.lax.pcast(x, axes, to="varying"), a)
            for a, ax in zip(args, in_axes)))

    def run(*args):
        m = next(jax.tree.leaves(a)[0].shape[0]
                 for a, ax in zip(args, in_axes) if ax == 0)
        shards_per_device(m, mesh, what=what)
        return jax.shard_map(
            body, mesh=mesh, out_specs=local, check_vma=check_vma,
            in_specs=tuple(local if ax == 0 else P() for ax in in_axes),
        )(*args)

    return run


def on_every_device(fn, mesh, check_vma: bool = True):
    """``fn`` on replicated operands, computed whole by every device of
    ``mesh`` (plain ``fn`` without one) — the single-graph paths (the
    full-graph forward of evaluation, the propagation baseline, the LLCG
    correction) on a mesh that shards the training state."""
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=check_vma)


def collective_pull(store: dict, send_offsets: jax.Array,
                    recv_positions: jax.Array, halo_size: int,
                    mesh, axis: str = "data") -> dict:
    """Ragged collective PULL: ship only the referenced slots.

    The ``shard_map`` form of :func:`pull_slab` for a store sharded
    slot-wise over ``axis``: every device owns ``k = M / mesh[axis]``
    contiguous owner shards (k = 1 is the classic one-part-per-device
    case; k > 1 is the M-exceeds-pod-size regime) and gathers from each
    of them the rows every requester's halo references (per the
    :class:`~repro.graph.partition.PullPlan`); a single ``all_to_all``
    routes them.  Per-pair lists are padded to the plan's max width K,
    so the wire carries ``M·M·K`` rows
    (:meth:`HaloSpec.collective_pull_nbytes`) — ≈ ``Σ_m |halo(G_m)|``
    for balanced partitions, vs the ``(M-1)·(B+1)`` rows of replicating
    the slab.

    Args:
      send_offsets:   (M, M, K) PullPlan.send_offsets.
      recv_positions: (M, M, K) PullPlan.recv_positions.
      halo_size: H — per-subgraph halo slots (slab gets H+1 rows).
    Returns the same pytree as :func:`pull_slab`.
    Raises ValueError when M is not a multiple of the exchange axes
    (pods · data on a multi-pod mesh).
    """
    axes = exchange_axes(mesh, axis)
    num_data = int(mesh.shape[axis])
    pods = int(mesh.shape["pod"]) if len(axes) == 2 else 1
    M, _, K = send_offsets.shape
    k = shards_per_device(M, mesh, axis, "collective_pull")
    l1, rows_total, hidden = store["data"].shape
    shard_rows = rows_total // M
    has_scale = "scale" in store

    def _pod_permute(g1):
        # g1 (p_r, d_o, b, a, K, l1, w): blocks my pod owns, keyed by
        # destination pod p_r.  Route them with pods-1 shifted ppermute
        # rounds over "pod" (ONE collective-permute per tensor on the
        # 2-pod production mesh) into (p_o, d_o, ...): blocks every pod
        # p_o owns that are destined for me.  Only this hop crosses the
        # inter-pod links, and each row ships exactly once.
        my = jax.lax.axis_index("pod")
        out = jax.lax.dynamic_update_index_in_dim(
            jnp.zeros_like(g1),
            jax.lax.dynamic_index_in_dim(g1, my, 0, keepdims=False),
            my, 0)
        for s in range(1, pods):
            dst = jax.lax.rem(my + s, pods)
            send = jax.lax.dynamic_index_in_dim(g1, dst, 0,
                                                keepdims=False)
            perm = [(i, (i + s) % pods) for i in range(pods)]
            rcv = jax.lax.ppermute(send, "pod", perm)
            src = jax.lax.rem(my - s + pods, pods)
            out = jax.lax.dynamic_update_index_in_dim(out, rcv, src, 0)
        return out

    def _exchange(table, send, recv, width, pad_value):
        # table (l1, k·shard_rows, width) — this device's k owner shards,
        # shard a at rows [a·shard_rows, (a+1)·shard_rows).
        # send (k, M, K): owner-local offsets for the k local owners;
        # recv (k, M, K): slab positions for the k local requesters.
        base = (jnp.arange(k, dtype=send.dtype)
                * shard_rows)[:, None, None]
        rows = table[:, (send + base).reshape(-1), :]      # (l1, k·M·K, w)
        # Flattened order is (owner-local a, requester m = e·k + b, K)
        # with the requester's combined block e = p_r·num_data + d_r.
        rows = rows.reshape(l1, k, pods, num_data, k, K, width)
        # Stage 1 (intra-pod): route by the requester's data coordinate.
        buf = jnp.transpose(rows, (3, 2, 4, 1, 5, 0, 6))
        got = jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)
        # got[d_o, p_r, b, a] = rows data-peer d_o of my pod ships toward
        # (pod p_r, my data column), requester-local b, its local shard a.
        got = jnp.swapaxes(got, 0, 1)                  # (p_r, d_o, b, a, …)
        if pods > 1:
            # Stage 2 (inter-pod): route by the requester's pod.
            got = _pod_permute(got)
        # got[p_o, d_o, b, a] = rows device (p_o, d_o) ships from its
        # local shard a to my local requester b — owner part
        # j = (p_o·num_data + d_o)·k + a, matching the (M, K) flattened
        # order of recv[b].
        vals = jnp.transpose(got, (2, 0, 1, 3, 4, 5, 6))
        vals = vals.reshape(k, M * K, l1, width)
        vals = jnp.moveaxis(vals, 1, 2)                    # (k, l1, M·K, w)
        slab = jnp.full((l1, halo_size + 1, width), pad_value, table.dtype)
        # Duplicate positions only occur at the sentinel row H, where
        # every routed value is an owner-sentinel zero row.
        return jax.vmap(
            lambda pos, v: slab.at[:, pos, :].set(v))(
                recv.reshape(k, M * K), vals)              # (k, l1, H+1, w)

    shard = P(None, axes, None)
    plan = P(axes, None, None)
    slab_spec = P(axes, None, None, None)

    if has_scale:
        def _body(data, scale, send, recv):
            return {"data": _exchange(data, send, recv, hidden, 0),
                    "scale": _exchange(scale, send, recv, 1, 1.0)}
        fn = jax.shard_map(_body, mesh=mesh,
                           in_specs=(shard, shard, plan, plan),
                           out_specs={"data": slab_spec,
                                      "scale": slab_spec})
        return fn(store["data"], store["scale"], send_offsets,
                  recv_positions)

    def _body(data, send, recv):
        return {"data": _exchange(data, send, recv, hidden, 0)}
    fn = jax.shard_map(_body, mesh=mesh, in_specs=(shard, plan, plan),
                       out_specs={"data": slab_spec})
    return fn(store["data"], send_offsets, recv_positions)


def push(store: dict, local_slots: jax.Array, local_valid: jax.Array,
         reps: jax.Array, sentinels: Optional[jax.Array] = None) -> dict:
    """Quantize + scatter fresh local boundary rows (Algorithm 1 lines 9–10).

    local_slots: (M, S) compact slot ids — part m's *own* sentinel row for
      non-boundary local nodes (the partitioner routes them there so every
      write stays inside the owner shard).
    local_valid: (M, S) bool; reps: (M, L-1, S, hidden) fp32.
    sentinels: (M,) per-part sentinel slots (re-zeroed after the scatter);
      defaults to the single last row for the unsharded layout.
    """
    data = store["data"]
    l1, rows, hidden = data.shape
    if sentinels is None:
        sentinels = jnp.asarray([rows - 1], jnp.int32)
    sentinels = jnp.asarray(sentinels, jnp.int32).reshape(-1)
    m, s = local_slots.shape
    per_part = sentinels if sentinels.size == m else sentinels[:1]
    fallback = jnp.broadcast_to(per_part.reshape(-1, 1), (m, s))
    ids = jnp.where(local_valid, local_slots, fallback).reshape(-1)
    vals = jnp.where(local_valid[:, None, :, None], reps, 0.0)
    q, scale = quantize_rows(vals, precision_of(store))
    q = jnp.swapaxes(q, 0, 1).reshape(l1, m * s, hidden)
    new = {"data": data.at[:, ids, :].set(q).at[:, sentinels, :].set(0)}
    if scale is not None:
        scale = jnp.swapaxes(scale, 0, 1).reshape(l1, m * s, 1)
        new["scale"] = (store["scale"].at[:, ids, :].set(scale)
                        .at[:, sentinels, :].set(1.0))
    return new


def _ef_residual(compensated: jax.Array, valid_mask: jax.Array,
                 precision: HaloPrecision) -> jax.Array:
    """New rounding residual of an error-feedback push: what the wire
    format lost of the (masked) compensated rows.  Invalid rows are 0 →
    residual 0.  Shared by every *_push_ef variant so the EF algebra
    (the telescoping invariant pinned in tests/test_halo_properties.py)
    lives in exactly one place."""
    masked = jnp.where(valid_mask, compensated, 0.0)
    q, scale = quantize_rows(masked, precision)
    return masked - dequantize_rows(q, scale)


def push_ef(store: dict, local_slots: jax.Array, local_valid: jax.Array,
            reps: jax.Array, residual: jax.Array,
            sentinels: Optional[jax.Array] = None) -> tuple[dict, jax.Array]:
    """Error-feedback PUSH: quantize ``reps + residual`` and carry the new
    rounding residual forward at the pusher (Bai et al. 2023 style).

    Deterministic round-to-nearest biases repeated pushes of
    slowly-moving representations; compensating each push with the
    previous rounding error keeps the time-averaged served value unbiased
    at the same wire cost.  ``residual`` has the shape of ``reps``;
    returns (new_store, new_residual).
    """
    compensated = reps + residual
    new_store = push(store, local_slots, local_valid, compensated,
                     sentinels)
    # Same masked tensor push() quantizes internally, so XLA CSEs the two
    # quantize passes under jit.
    return new_store, _ef_residual(compensated,
                                   local_valid[:, None, :, None],
                                   precision_of(store))


def shard_push(store: dict, local_slots: jax.Array, local_valid: jax.Array,
               reps: jax.Array, shard_rows: int, mesh,
               axis: str = "data") -> dict:
    """Explicit shard-local PUSH under ``shard_map``: each device scatters
    the rows of its ``k = M / mesh[axis]`` resident parts with owner-local
    offsets into its own k shards — structurally incapable of writing
    another device's slots.  :func:`push` is the SPMD fallback (same
    math, the partitioner already routes every row into the owner shard,
    but XLA cannot *prove* it and may materialize cross-device traffic).
    Works on single- and multi-pod meshes alike — the scatter is device-
    local on any mesh shape, only the combined block index e = p·data + d
    changes.  Raises ValueError when M is not a multiple of the
    exchange axes."""
    axes = exchange_axes(mesh, axis)
    M = local_slots.shape[0]
    k = shards_per_device(M, mesh, axis, "shard_push")
    prec = precision_of(store)
    has_scale = "scale" in store

    def _scatter(data, scale, slots, valid, reps_blk):
        # data (l1, k·shard_rows, hid) — this device's k shards; slots /
        # valid (k, S); reps_blk (k, l1, S, hid).  Local part a (global
        # part j = e·k + a) owns rows [a·shard_rows, (a+1)·shard_rows);
        # its slots all lie inside shard j by construction.
        e = _combined_index(mesh, axis)
        sent_local = (jnp.arange(k, dtype=jnp.int32) + 1) * shard_rows - 1
        off = jnp.where(valid, slots - e * (k * shard_rows),
                        sent_local[:, None])               # (k, S)
        vals = jnp.where(valid[:, None, :, None], reps_blk, 0.0)
        q, sc = quantize_rows(vals, prec)
        l1 = data.shape[0]
        qs = jnp.moveaxis(q, 1, 0).reshape(l1, -1, q.shape[-1])
        new = {"data": data.at[:, off.reshape(-1), :].set(qs)
               .at[:, sent_local, :].set(0)}
        if sc is not None:
            scs = jnp.moveaxis(sc, 1, 0).reshape(l1, -1, 1)
            new["scale"] = (scale.at[:, off.reshape(-1), :].set(scs)
                            .at[:, sent_local, :].set(1.0))
        return new

    shard = P(None, axes, None)
    m_spec = P(axes, None)
    reps_spec = P(axes, None, None, None)

    if has_scale:
        fn = jax.shard_map(_scatter, mesh=mesh,
                           in_specs=(shard, shard, m_spec, m_spec,
                                     reps_spec),
                           out_specs={"data": shard, "scale": shard})
        return fn(store["data"], store["scale"], local_slots, local_valid,
                  reps)

    def _body(data, slots, valid, reps_blk):
        return _scatter(data, None, slots, valid, reps_blk)

    fn = jax.shard_map(_body, mesh=mesh,
                       in_specs=(shard, m_spec, m_spec, reps_spec),
                       out_specs={"data": shard})
    return fn(store["data"], local_slots, local_valid, reps)


def shard_push_ef(store: dict, local_slots: jax.Array,
                  local_valid: jax.Array, reps: jax.Array,
                  residual: jax.Array, shard_rows: int, mesh,
                  axis: str = "data") -> tuple[dict, jax.Array]:
    """Error-feedback form of :func:`shard_push` (see :func:`push_ef`).

    The scatter goes through the shard-local path; the residual update is
    elementwise over the (M, ...)-sharded ``reps``/``residual`` and needs
    no communication at all.  (The quantize here cannot be CSE'd against
    the one inside the shard_map body, so push epochs pay it twice —
    push epochs are 1-in-N and the pass is elementwise, cheap next to
    the epoch's matmuls.)"""
    compensated = reps + residual
    new_store = shard_push(store, local_slots, local_valid, compensated,
                           shard_rows, mesh, axis)
    return new_store, _ef_residual(compensated,
                                   local_valid[:, None, :, None],
                                   precision_of(store))


def owner_push(store: dict, owner: jax.Array, local_slots: jax.Array,
               local_valid: jax.Array, reps: jax.Array,
               shard_rows: int) -> dict:
    """Single-part PUSH that only ever touches the owner's shard.

    The DIGEST-A worker form of :func:`shard_push`: slice shard ``owner``
    out of the slab, scatter with owner-local offsets, write the shard
    back — a ``dynamic_update_slice`` of exactly ``shard_rows`` rows, so
    the write region is provably inside the owner's shard (no whole-slab
    scatter for the partitioner to reason about).  Addresses the slab by
    owner *part*, never by device, so it is independent of how the M
    shards are laid over mesh axes — the same worker push works whether
    the store is placed on one device, a "data" axis, or the combined
    multi-pod ("pod", "data") axes.

    local_slots: (S,) global store slots of this worker's local rows
      (its own sentinel at non-boundary rows); local_valid: (S,) bool;
    reps: (L-1, S, hidden) fp32.
    """
    data = store["data"]
    l1, _, hidden = data.shape
    start = jnp.asarray(owner, jnp.int32) * shard_rows
    off = jnp.where(local_valid, local_slots - start, shard_rows - 1)
    vals = jnp.where(local_valid[None, :, None], reps, 0.0)
    q, sc = quantize_rows(vals, precision_of(store))
    shard = jax.lax.dynamic_slice(data, (0, start, 0),
                                  (l1, shard_rows, hidden))
    shard = shard.at[:, off, :].set(q).at[:, -1, :].set(0)
    new = {"data": jax.lax.dynamic_update_slice(data, shard,
                                                (0, start, 0))}
    if sc is not None:
        sshard = jax.lax.dynamic_slice(store["scale"], (0, start, 0),
                                       (l1, shard_rows, 1))
        sshard = sshard.at[:, off, :].set(sc).at[:, -1, :].set(1.0)
        new["scale"] = jax.lax.dynamic_update_slice(
            store["scale"], sshard, (0, start, 0))
    return new


def owner_push_ef(store: dict, owner: jax.Array, local_slots: jax.Array,
                  local_valid: jax.Array, reps: jax.Array,
                  residual: jax.Array, shard_rows: int
                  ) -> tuple[dict, jax.Array]:
    """Error-feedback form of :func:`owner_push` (see :func:`push_ef`)."""
    compensated = reps + residual
    new_store = owner_push(store, owner, local_slots, local_valid,
                           compensated, shard_rows)
    return new_store, _ef_residual(compensated,
                                   local_valid[None, :, None],
                                   precision_of(store))


def shard_staleness_error(store: dict, fresh: jax.Array,
                          local_slots: jax.Array, served: jax.Array,
                          shard_rows: int, mesh, axis: str = "data"
                          ) -> jax.Array:
    """:func:`staleness_error` with owner-local reads under ``shard_map``.

    The SPMD form gathers ``store[:, local_slots, :]`` with the slot axis
    sharded — every part only ever reads its *own* shard, but XLA cannot
    prove it and materializes an all-gather of the whole slab each epoch.
    Here each device reads the rows of its k resident parts straight out
    of its local shards; only the final (L-1,)-sized max crosses devices.
    Same numbers as :func:`staleness_error` (max is order-free; the
    gathers do no arithmetic).  Mesh-shape agnostic like
    :func:`shard_push`: reads stay inside the device's own shards on
    single- and multi-pod meshes (combined block index e = p·data + d).
    """
    axes = exchange_axes(mesh, axis)
    M, S = local_slots.shape
    k = shards_per_device(M, mesh, axis, "shard_staleness_error")
    has_scale = "scale" in store
    l1 = store["data"].shape[0]

    def _body(data, scale, fresh_blk, slots, served_blk):
        # data (l1, k·shard_rows, h); fresh_blk (k, l1, S, h); slots /
        # served_blk (k, S).  Every slot of a resident part lies inside
        # this device's block (non-boundary rows hit the owner sentinel).
        e = _combined_index(mesh, axis)
        off = (slots - e * (k * shard_rows)).reshape(-1)
        stale = data[:, off, :].astype(jnp.float32)        # (l1, k·S, h)
        if scale is not None:
            stale = stale * scale[:, off, :]
        stale = jnp.moveaxis(stale.reshape(l1, k, S, -1), 1, 0)
        diff = jnp.linalg.norm(fresh_blk - stale, axis=-1)  # (k, l1, S)
        diff = jnp.where(served_blk[:, None, :], diff, 0.0)
        return jnp.max(diff, axis=(0, 2))[None]            # (1, l1)

    shard = P(None, axes, None)
    m_spec = P(axes, None)
    reps_spec = P(axes, None, None, None)
    out_spec = P(axes, None)

    if has_scale:
        fn = jax.shard_map(_body, mesh=mesh,
                           in_specs=(shard, shard, reps_spec, m_spec,
                                     m_spec),
                           out_specs=out_spec)
        per_dev = fn(store["data"], store["scale"], fresh, local_slots,
                     served)
    else:
        def _nb(data, fresh_blk, slots, served_blk):
            return _body(data, None, fresh_blk, slots, served_blk)
        fn = jax.shard_map(_nb, mesh=mesh,
                           in_specs=(shard, reps_spec, m_spec, m_spec),
                           out_specs=out_spec)
        per_dev = fn(store["data"], fresh, local_slots, served)
    # (num_devices, L-1) sharded partial maxima → tiny all-reduce.
    return jnp.max(per_dev, axis=0)


def staleness_error(store: dict, fresh: jax.Array, local_slots: jax.Array,
                    served: jax.Array) -> jax.Array:
    """ε^(ℓ) = max_v ‖h_v^(ℓ) − h̃_v^(ℓ)‖₂ over *served* (boundary) rows.

    fresh: (M, L-1, S, hidden) this epoch's representations.
    served: (M, S) bool — valid local rows present in the compact store
      (``StackedPartitions.local_boundary``): exactly the rows whose
      staleness other subgraphs can observe (Theorem 1 only involves
      pulled halo rows).
    Returns (L-1,) per-hidden-layer max error.
    """
    stale = pull(store, local_slots)                   # (M, L-1, S, h)
    diff = jnp.linalg.norm(fresh - stale, axis=-1)     # (M, L-1, S)
    diff = jnp.where(served[:, None, :], diff, 0.0)
    return jnp.max(diff, axis=(0, 2))
