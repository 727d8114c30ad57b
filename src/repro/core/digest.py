"""DIGEST — synchronous distributed GNN training with periodic stale sync.

One code path implements all three framework families the paper compares
(§2, Fig. 1) by swapping what the out-of-subgraph halo tables contain:

  mode="digest"       stale reps pulled from the store every N epochs (ours)
  mode="partition"    nothing — cross-subgraph edges dropped (LLCG-family)
  mode="propagation"  fresh reps recomputed and exchanged every epoch
                      (DistDGL-family; exact but communication-heavy)

The epoch function is a single jitted SPMD program: subgraphs are vmapped on
CPU and sharded over the mesh "data" axis under pjit (see
repro.launch.train_gnn), which is the Algorithm-1 `for m in parallel` loop.

Stale state lives in the compact **owner-sharded** HaloExchange store
(boundary rows only, grouped by owning part, pluggable fp32/bf16/int8
precision — see repro.core.halo_exchange).  A PULL epoch gathers each
subgraph's halo rows into a device-local slab ``(M, L-1, H+1, hidden)``
— via the XLA-partitioned dense gather (all-gather fallback) or the
explicit ragged ``collective_pull`` when a mesh is supplied (any M that
is a multiple of the mesh "data" axis: each device then carries
k = M/devices subgraphs and owner shards) — and non-pull epochs read
that local slice *directly* through the fused pull+aggregate kernel:
nothing is replicated and no fp32 halo cache is ever materialized.

Under ``pull_mode="collective"`` the epoch is fully SPMD end to end:
PULL is the ragged ``all_to_all``, PUSH goes through the shard-local
``shard_push`` (owner-local offsets — structurally incapable of
cross-device writes), and the Theorem-1 staleness probe reads each
device's own shards (``shard_staleness_error``).  The compiled epoch
then contains *no* cross-device scatter/gather for the halo state at
all — a regression-tested invariant (tests/test_hlo_collectives.py),
not a partitioner heuristic.

The same ``pull_mode="collective"`` covers the multi-pod production
mesh: when the supplied mesh carries a "pod" axis, the halo-exchange
paths auto-detect it, shard M over the combined ("pod", "data") axes
(k = M/(pods·data) subgraphs and owner shards per device) and run the
PULL as the two-stage intra-pod ``all_to_all`` + inter-pod ``ppermute``
exchange — bitwise-equal to the single-pod collective and the dense
gather (tests/test_multipod.py; see the routing-table section of
``repro.core.halo_exchange``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt_io
from repro.core import faults as faults_mod
from repro.core import halo_exchange
from repro.core import predictor as predictor_mod
from repro.core.halo_exchange import HaloPrecision
from repro.core.predictor import PredictorConfig
from repro.graph.graph import Graph
from repro.graph.partition import StackedPartitions, build_partitions
from repro.kernels.spmm import BLOCK_ROWS, STREAM_CHUNK_ROWS, vma_checkable
from repro.models.gnn import (GNNConfig, gnn_forward, gnn_forward_sampled,
                              gnn_specs, halo_ref, projected_halo_ref)
from repro.nn import init_params, micro_f1, softmax_cross_entropy
from repro.optim import Optimizer

Pytree = Any

MODES = ("digest", "partition", "propagation")

# Named scopes of the epoch's parts (Algorithm 1): they name every device
# op in the compiled program's op metadata, so a profile of training reads
# the inputs, the pull, the loss (forward and backward, with each layer's
# ``aggregate``/``transform``/``attention`` scopes of repro.models.gnn
# inside it), the optimizer, the push and the metrics apart.  The pull's,
# push's, optimizer's and metrics' scopes sit by their functions below.
INPUTS_SCOPE = "digest/inputs"      # round number, layer-0 features
LOSS_SCOPE = "digest/loss"          # per-subgraph value_and_grad


def gat_projected(cfg: GNNConfig) -> bool:
    """True when the epoch runs GAT with the owner-shard projection dedup:
    the pulled cache then holds *projected* rows (z = W·h̃ per hidden
    layer, flat ``z{ell}``/``z{ell}_scale`` slabs) instead of raw stale
    representations.  Must agree between :func:`init_state` and
    :func:`make_epoch_fn` — hence one predicate."""
    return (cfg.model == "gat" and cfg.gat_halo_dedup
            and cfg.num_layers > 1)


# ---------------------------------------------------------------------------
# Data preparation
# ---------------------------------------------------------------------------

def prepare_graph_data(g: Graph, num_parts: int, method: str = "greedy",
                       seed: int = 0, halo_weight: float = 0.0,
                       stream_chunk_rows: int = None,
                       order: str = "none") -> dict:
    """Build the jnp data dict consumed by the epoch function.

    ``halo_weight`` enables the boundary-aware partitioning score (see
    :func:`repro.graph.partition.greedy_partition`); ``stream_chunk_rows``
    sets the chunk geometry of the precomputed halo worklists (defaults
    to the kernel's ``STREAM_CHUNK_ROWS``).  ``order="rcm"`` applies the
    locality-aware local-row reorder (``build_partitions(order=...)``),
    guarded at the same chunk geometry the epoch streams with so the
    worklist occupancy can only drop; the full M=1 eval view always
    stays at ``order="none"`` — ``evaluate``/``full_graph_forward`` are
    untouched by the knob.
    """
    chunk_rows = (STREAM_CHUNK_ROWS if stream_chunk_rows is None
                  else stream_chunk_rows)
    sp = build_partitions(g, num_parts, method=method, seed=seed,
                          halo_weight=halo_weight, order=order,
                          order_chunk_rows=chunk_rows)
    full = build_partitions(g, 1, method="random", seed=seed)
    x_global = np.concatenate(
        [g.features, np.zeros((1, g.features.shape[1]), np.float32)], axis=0)

    def _struct(s: StackedPartitions) -> tuple:
        # The out-ELL in per-subgraph halo-slot space addresses the
        # device-local pulled slabs directly; the store-slot / global-id
        # remaps live on StackedPartitions for whole-slab consumers.
        # The chunk worklist rides along with the adjacency it was
        # computed from: the streamed halo_spmm skips every
        # (row_block, chunk) pair it proves empty (geometry: the kernels'
        # 128-row blocks over the BLOCK_ROWS-padded S rows, chunk_rows-
        # row chunks over the (H+1)-row slab).
        wl = s.chunk_worklist(chunk_rows, BLOCK_ROWS)
        return {"in_nbr": jnp.asarray(s.in_nbr),
                "in_wts": jnp.asarray(s.in_wts),
                "out_nbr": jnp.asarray(s.out_nbr),
                "out_wts": jnp.asarray(s.out_wts),
                "wl_ids": jnp.asarray(wl.ids),
                "wl_cnt": jnp.asarray(wl.cnt)}, wl

    struct, worklist = _struct(sp)
    full_struct, _ = _struct(full)

    plan = sp.pull_plan()
    # halo_ids extended with a sentinel column: gathering x_global (or the
    # full-graph reps) at these ids yields the per-subgraph (H+1)-row halo
    # slab directly, row H the zero sentinel.
    halo_ids_x = np.concatenate(
        [sp.halo_ids, np.full((sp.num_parts, 1), g.num_nodes, np.int32)],
        axis=1)
    return {
        "x_global": jnp.asarray(x_global),
        "struct": struct,
        "local_ids": jnp.asarray(sp.local_ids),
        "local_valid": jnp.asarray(sp.local_valid),
        "halo_ids": jnp.asarray(sp.halo_ids),
        "halo_valid": jnp.asarray(sp.halo_valid),
        "halo_ids_x": jnp.asarray(halo_ids_x),
        # Owner-sharded compact-store views (HaloExchange slot space).
        "local_slots": jnp.asarray(sp.local_slots),
        "local_boundary": jnp.asarray(sp.local_boundary),
        "halo_slots": jnp.asarray(sp.halo_slots),
        "store_ids": jnp.asarray(sp.store_ids),
        "sentinel_slots": jnp.asarray(sp.sentinel_slots),
        # Ragged collective-pull routing (PullPlan).
        "pull_send": jnp.asarray(plan.send_offsets),
        "pull_recv": jnp.asarray(plan.recv_positions),
        "labels": jnp.asarray(sp.labels),
        "train_mask": jnp.asarray(sp.train_mask),
        "val_mask": jnp.asarray(sp.val_mask),
        "test_mask": jnp.asarray(sp.test_mask),
        # Full-graph (M=1) view for exact eval / propagation mode.
        "full_struct": full_struct,
        "full_ids": jnp.asarray(full.local_ids),
        "full_valid": jnp.asarray(full.local_valid),
        "full_labels": jnp.asarray(full.labels),
        "full_train_mask": jnp.asarray(full.train_mask),
        "full_val_mask": jnp.asarray(full.val_mask),
        "full_test_mask": jnp.asarray(full.test_mask),
        # Host-side metadata (not traced).  _worklist carries the static
        # occupancy the launchers copy into GNNConfig.halo_occupancy for
        # the skip-vs-dense stream selection.
        "_sp": sp,
        "_graph": g,
        "_worklist": worklist,
    }


def _subgraph_features(x_global: jax.Array, ids: jax.Array) -> jax.Array:
    return x_global[ids]


def check_worklist_geometry(cfg: GNNConfig, data: dict) -> None:
    """Reject a chunk worklist built at a different ``chunk_rows`` than
    the epoch's kernels will stream with — a coarser worklist silently
    drops referenced slab rows (a finer one the kernel catches itself),
    so the build knob (``prepare_graph_data(stream_chunk_rows=...)``)
    and the call knob (``GNNConfig.stream_chunk_rows``) must agree.
    No-op when the host-side ``_worklist`` meta was stripped."""
    wl = data.get("_worklist")
    if wl is None:
        return
    want = (cfg.stream_chunk_rows if cfg.stream_chunk_rows is not None
            else STREAM_CHUNK_ROWS)
    if wl.chunk_rows != want:
        raise ValueError(
            f"chunk worklist was built with chunk_rows={wl.chunk_rows} "
            f"but the epoch streams with chunk_rows={want} — pass the "
            f"same value to prepare_graph_data(stream_chunk_rows=...) "
            f"and GNNConfig.stream_chunk_rows (a mismatched worklist "
            f"would silently skip referenced slab rows)")


def check_collective_geometry(data: dict, mesh, axis: str = "data") -> int:
    """Fail fast — before trace time — when the partition count cannot be
    laid over the mesh's halo-exchange axes; returns k = parts/device.

    The collective paths shard M over *every* exchange axis
    (``halo_exchange.exchange_axes``: the "data" axis alone, or the
    combined ("pod", "data") axes on a multi-pod mesh), so M must be a
    whole multiple of pods·data.  The shard_map bodies would raise the
    same spelled-out ValueError at trace time; calling this at launch /
    train start surfaces it before any compilation work.  Works on real
    and abstract (ShapeDtypeStruct) data dicts alike — only shapes are
    read.
    """
    num_parts = int(data["local_slots"].shape[0])
    return halo_exchange.shards_per_device(num_parts, mesh, axis,
                                           "pull_mode='collective'")


def project_store_tables(store: dict, params: Pytree, cfg: GNNConfig,
                         precision: HaloPrecision, pstore: dict = None,
                         gamma: float = 1.0) -> dict:
    """GAT owner-shard projection dedup: project the *store*, not the slabs.

    For every hidden layer ℓ, computes ``z{ℓ} = dequant(store[ℓ]) · W_{ℓ+1}``
    over the R owner-sharded slot rows — ONCE per owner shard per layer —
    and re-encodes it in the wire precision, returning pull-ready
    single-layer stores ``{"z{ℓ}": {"data": (1, R, heads·dh)[, "scale"]}}``
    for :func:`halo_exchange.pull_slab` / ``collective_pull``.  The legacy
    path instead re-projected every subgraph's pulled ``(H+1, d)`` slab
    every epoch — ~M× the FLOPs, since each boundary row appears in many
    subgraphs' halos.  The einsum and the per-row quantization are
    row-wise over the slot axis, so under pjit with the store sharded
    slot-wise they stay inside each device's shards (no collectives); the
    projected rows then ship through the *same* pull routing as raw rows.
    Shipping ``heads·dh``-wide projected rows also shrinks pull bytes
    whenever ``heads·head_dim < hidden``.

    With a SAT predictor history (``pstore``/``gamma`` — see
    ``repro.core.predictor``) the rows are staleness-alleviated BEFORE
    the projection: ``(h̃ + γ·δ)·W = h̃·W + γ·δ·W`` by linearity, so the
    dedup path gets prediction at zero extra wire tensors — the z-cache
    structure (and the pull census) is unchanged.
    """
    out = {}
    for ell in range(cfg.num_layers - 1):
        w = params[f"layer_{ell + 1}"]["w"]        # (hidden, heads, dh)
        tab, sc = halo_exchange.layer_table(store, ell)
        rows = halo_exchange.dequantize_rows(tab, sc)       # (R, hidden)
        if pstore is not None:
            ptab, psc = halo_exchange.layer_table(pstore, ell)
            rows = rows + (jnp.float32(gamma)
                           * halo_exchange.dequantize_rows(ptab, psc))
        z = jnp.einsum("rd,dhk->rhk", rows, w)
        z = z.reshape(z.shape[0], -1)                       # (R, heads·dh)
        q, qs = halo_exchange.quantize_rows(z, precision)
        zs = {"data": q[None]}
        if qs is not None:
            zs["scale"] = qs[None]
        out[f"z{ell}"] = zs
    return out


# ---------------------------------------------------------------------------
# Single-subgraph loss (shared by every mode and by DIGEST-A)
# ---------------------------------------------------------------------------

def make_subgraph_loss(cfg: GNNConfig):
    def loss_fn(params, x_local, halo_tables, struct, labels, mask):
        tables = [jax.lax.stop_gradient(t) for t in halo_tables]
        logits, push = gnn_forward(cfg, params, x_local, tables, struct)
        loss = softmax_cross_entropy(logits, labels, mask)
        return loss, (jnp.stack(push) if push else
                      jnp.zeros((0,) + x_local.shape), logits)
    return loss_fn


def empty_halo_struct(cfg: GNNConfig, struct: dict, rows: int = 8
                      ) -> tuple[list, dict]:
    """Per-layer all-zero halo tables + a struct whose out-ELL is remapped
    into them — the "no out-of-subgraph information" view a single-
    subgraph forward needs when every ``out_nbr`` entry is a sentinel
    (the M=1 full-graph view, and the serving per-part top layer when
    the halo side is supplied separately).  The zero tables contribute
    exact ±0.0 terms, so consumers stay bitwise-comparable with paths
    that drop the halo side entirely."""
    tables = [jnp.zeros((rows, cfg.in_dim), jnp.float32)]
    tables += [jnp.zeros((rows, cfg.hidden_dim), jnp.float32)
               for _ in range(cfg.num_layers - 1)]
    struct = dict(struct)
    struct["out_nbr"] = jnp.minimum(struct["out_nbr"], rows)
    return tables, struct


def full_graph_forward(cfg: GNNConfig, params: Pytree, data: dict,
                       mesh=None) -> jax.Array:
    """Exact (no staleness, no partition) forward; returns (logits
    (N_pad, classes), reps).  With ``mesh`` every device computes it
    whole (:func:`halo_exchange.on_every_device`)."""
    def forward(params, x_global, full_ids, full_struct):
        x = _subgraph_features(x_global, full_ids[0])
        # Halo is empty in the M=1 view: all out_nbr are sentinels. Supply
        # small correctly-shaped zero tables and remap sentinels into them.
        struct = {k: v[0] for k, v in full_struct.items()}
        tables, struct = empty_halo_struct(cfg, struct)
        return gnn_forward(cfg, params, x, tables, struct)

    return halo_exchange.on_every_device(
        forward, mesh, check_vma=vma_checkable(cfg.backend))(
        params, data["x_global"], data["full_ids"], data["full_struct"])


def top_layer_reps(cfg: GNNConfig, params: Pytree, data: dict) -> jax.Array:
    """h^(L-1) for every node — the exact full-graph input rows of the
    top GNN layer, in the full view's global-id row order (N_pad, hidden).

    This is what a serving-store refresh pushes (``repro.core.serving``):
    the store then answers any node's prediction by gathering these rows
    and running only layer L-1.  It is byte-for-byte ``reps[-1]`` of
    :func:`full_graph_forward` — the same tensor the training epoch
    PUSHes for layer L-2 — so serving parity against ``evaluate()`` is
    exact rather than approximate."""
    if cfg.num_layers < 2:
        raise ValueError("serving from stored representations needs "
                         "num_layers >= 2 (a 1-layer GNN reads raw "
                         "features; there is no (L-1)-layer row to store)")
    _, reps = full_graph_forward(cfg, params, data)
    return reps[-1]


# ---------------------------------------------------------------------------
# The DIGEST epoch (Algorithm 1, one global round r)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainSettings:
    sync_interval: int = 10          # N of Algorithm 1
    mode: str = "digest"
    pull_on_first_epoch: bool = False  # paper pulls only at r % N == 0
    # Wire/storage precision of the HaloExchange store (§3.3 byte counts).
    precision: HaloPrecision = HaloPrecision()
    # PULL transport: "gather" = dense gather (XLA inserts the all-gather
    # under pjit; exact on any device count), "collective" = the fully
    # SPMD shard_map epoch — ragged all_to_all pulls of only the
    # referenced slots, shard-local pushes and staleness reads (pass the
    # mesh to make_epoch_fn; needs num_parts to be a multiple of the
    # exchange axes — the "data" axis, times "pod" on a multi-pod mesh
    # where the pull runs the two-stage intra-pod/inter-pod exchange:
    # k = parts/devices subgraphs + owner shards per device).
    pull_mode: str = "gather"
    # LLCG-style server correction (for the partition-based baseline): one
    # extra server-side gradient step per round on a sampled node batch
    # with FULL neighbor information [Ramezani et al. 2021].
    llcg_correction: bool = False
    correction_frac: float = 0.1
    correction_lr: float = 1e-3
    # Mini-batch sampled regime (make_sampled_epoch_fn): "cv" aggregates
    # unsampled neighbors from the stale history (VR-GCN control
    # variates); "plain" drops the history term — classic scaled neighbor
    # sampling, the variance-benchmark baseline.
    sample_estimator: str = "cv"
    # Bounded-staleness watchdog: when a shard's last successful push is
    # >= max_staleness rounds old, its push is forced on the next round
    # regardless of the sync cadence or the fault mask — Theorems 1/3
    # assume bounded staleness, so the watchdog converts "arbitrarily
    # stale under faults" back into the regime the analysis covers.
    # Requires the fault-aware state leaves (faults.attach_fault_state);
    # None disables the watchdog.
    max_staleness: Optional[int] = None
    # Staleness-alleviated embedding prediction (SAT; see
    # repro.core.predictor): consumers read ``dequant(store row) +
    # γ·dequant(pstore row)`` where the pstore carries each row's
    # last-sync delta (or its β-EMA), maintained shard-locally at push
    # time and exchanged through the exact same pull routing as the
    # store.  ``kind="none"`` creates NO extra leaves and compiles the
    # bitwise-identical predictor-free program.
    predictor: PredictorConfig = PredictorConfig()


PULL_SCOPE = "digest/pull"


@jax.named_scope(PULL_SCOPE)
def _digest_pull(cfg: GNNConfig, settings: TrainSettings, state: dict,
                 data: dict, mesh, r) -> dict:
    """Algorithm-1 PULL (line 5): gather each subgraph's halo slots from
    the owner shards into the device-local cache slab every
    ``sync_interval`` epochs.  ONE implementation shared by the
    full-batch epoch and the sampled step — both therefore compile to
    the identical collective routing (the ragged all_to_all census the
    HLO tests pin is a property of this function, not of the caller).

    Returns ``(cache, pcache)``: the stale slab plus the pulled SAT
    predictor slab (``None`` unless the predictor is enabled on a
    non-dedup model — the pstore rides the same routing, one extra
    exchange per store tensor).  Under the GAT dedup the prediction is
    folded into :func:`project_store_tables` *before* projection, so
    the z-cache and the pull census stay exactly as without it."""
    halo_size = data["halo_ids"].shape[1]
    do_pull = (r % settings.sync_interval == 0)
    if settings.pull_on_first_epoch:
        do_pull = do_pull | (r == 1)
    pred = settings.predictor.enabled and "pstore" in state
    if settings.pull_mode == "collective":
        def _pull_store(zs):
            return halo_exchange.collective_pull(
                zs, data["pull_send"], data["pull_recv"],
                halo_size, mesh)
    else:
        def _pull_store(zs):
            return halo_exchange.pull_slab(zs, data["halo_slots"])
    if gat_projected(cfg):
        def _pull():
            # Owner-shard projection (once per layer) + the same
            # ragged routing, one exchange per z tensor.
            new_cache = {}
            for key, zs in project_store_tables(
                    state["store"], state["params"], cfg,
                    settings.precision,
                    pstore=state["pstore"] if pred else None,
                    gamma=settings.predictor.gamma).items():
                slab = _pull_store(zs)
                new_cache[key] = slab["data"]
                if "scale" in slab:
                    new_cache[f"{key}_scale"] = slab["scale"]
            return new_cache, state.get("pcache")
    elif pred:
        def _pull():
            return _pull_store(state["store"]), _pull_store(state["pstore"])
    else:
        def _pull():
            return _pull_store(state["store"]), None
    return jax.lax.cond(do_pull, _pull,
                        lambda: (state["cache"], state.get("pcache")))


PUSH_SCOPE = "digest/push"


@jax.named_scope(PUSH_SCOPE)
def _digest_push(cfg: GNNConfig, settings: TrainSettings, state: dict,
                 data: dict, push_reps, mesh, r) -> tuple:
    """Periodic PUSH (Algorithm 1 lines 9–10; epochs r = 1, N+1, 2N+1,
    ...) + the Theorem-1 staleness probe; shared by the full-batch epoch
    and the sampled step.  Owner-sharded scatter: every row of part m
    lands in shard m.  Collective mode routes it through the explicit
    shard-local forms (shard_push / shard_staleness_error) so the
    compiled epoch carries ZERO cross-device push traffic — the SPMD
    scatter/gather fallback is the partitioner-dependent path (same
    math, but XLA cannot prove writes stay in-shard and materializes
    collectives around them).

    Fault-aware when ``state`` carries the ``faults.attach_fault_state``
    leaves: the host-refreshed per-shard ``push_ok`` mask AND-gates each
    shard's rows into the *same* compiled scatter (masked rows route to
    the shard's sentinel slot, so the store keeps last-known-good
    contents — no program change, census identical), and the per-shard
    ``last_push_round`` age table records successful pushes so
    fault-induced staleness is measured rather than silent.  With
    ``settings.max_staleness`` set, shards whose age reaches the bound
    are force-pushed on the next round even off-cadence (the blocking
    resync the Theorem-1/3 bounded-staleness analysis needs).  Without
    the fault leaves the exact pre-fault program compiles.

    With the SAT predictor enabled this also advances the push-side
    history (``state["predictor"]``, gated by the SAME per-part ok mask
    as the store push, so fault-masked shards freeze and degraded pulls
    extrapolate from the last-known-good delta), scatters the resulting
    delta rows into the pstore through the identical push path, and
    measures eps against the *predicted* rows — the residual staleness
    error consumers actually see — via a virtual fp32 store
    ``dequant(store) + γ·dequant(pstore)`` (elementwise, so the probe's
    shard-local reads are untouched).

    Returns (store, push_residual, eps, last_push_round, pstore,
    predictor_history)."""
    new_store = state["store"]
    new_residual = state.get("push_residual")
    new_last = state.get("last_push_round")
    new_pstore = state.get("pstore")
    new_hist = state.get("predictor")
    eps = jnp.zeros((max(cfg.num_layers - 1, 1),), jnp.float32)
    if settings.mode == "digest" and cfg.num_layers > 1:
        do_push = ((r - 1) % settings.sync_interval == 0)
        num_parts = data["local_slots"].shape[0]
        shard_rows = state["store"]["data"].shape[1] // num_parts
        local_valid = data["local_valid"]
        ok = jnp.broadcast_to(do_push, (num_parts,))          # (M,)
        if new_last is not None:
            ok = do_push & state["push_ok"]                    # (M,)
            if settings.max_staleness is not None:
                ok = ok | ((r - new_last) >= settings.max_staleness)
            do_push = jnp.any(ok)
            local_valid = local_valid & ok[:, None]
            new_last = jnp.where(ok, jnp.asarray(r, new_last.dtype),
                                 new_last)
        pred = settings.predictor.enabled and new_pstore is not None
        eps = _staleness(settings, state, data, push_reps, pred,
                         shard_rows, mesh)
        if settings.pull_mode == "collective":
            def _push():
                return halo_exchange.shard_push(
                    state["store"], data["local_slots"],
                    local_valid, push_reps, shard_rows, mesh)

            def _push_ef():
                return halo_exchange.shard_push_ef(
                    state["store"], data["local_slots"],
                    local_valid, push_reps,
                    state["push_residual"], shard_rows, mesh)
        else:
            def _push():
                return halo_exchange.push(
                    state["store"], data["local_slots"],
                    local_valid, push_reps,
                    data["sentinel_slots"])

            def _push_ef():
                return halo_exchange.push_ef(
                    state["store"], data["local_slots"],
                    local_valid, push_reps,
                    state["push_residual"], data["sentinel_slots"])
        if settings.precision.error_feedback:
            new_store, new_residual = jax.lax.cond(
                do_push, _push_ef,
                lambda: (state["store"], state["push_residual"]))
            if new_last is not None:
                # A masked shard wrote nothing, so its EF residual must
                # not absorb this round's quantization error either.
                new_residual = jnp.where(ok[:, None, None, None],
                                         new_residual,
                                         state["push_residual"])
        else:
            new_store = jax.lax.cond(do_push, _push,
                                     lambda: state["store"])
        if pred:
            # History transition + pstore scatter, gated exactly like
            # the store push (pure in the accepted-push sequence; no EF
            # on the pstore — deltas do not telescope across pushes).
            new_hist, prows = predictor_mod.update_history(
                state["predictor"], push_reps, ok, settings.predictor)
            if settings.pull_mode == "collective":
                def _ppush():
                    return halo_exchange.shard_push(
                        state["pstore"], data["local_slots"],
                        local_valid, prows, shard_rows, mesh)
            else:
                def _ppush():
                    return halo_exchange.push(
                        state["pstore"], data["local_slots"],
                        local_valid, prows, data["sentinel_slots"])
            new_pstore = jax.lax.cond(do_push, _ppush,
                                      lambda: state["pstore"])
    return new_store, new_residual, eps, new_last, new_pstore, new_hist


STALENESS_SCOPE = "staleness"       # nested in PUSH_SCOPE


@jax.named_scope(STALENESS_SCOPE)
def _staleness(settings: TrainSettings, state: dict, data: dict, push_reps,
               pred: bool, shard_rows: int, mesh) -> jax.Array:
    """The Theorem-1 probe: eps per hidden layer, measured against the
    rows consumers read (the store, or with the SAT predictor the virtual
    fp32 store ``dequant(store) + γ·dequant(pstore)``)."""
    eps_store = state["store"]
    if pred:
        eps_store = {"data": (
            halo_exchange.dequantize_rows(
                state["store"]["data"], state["store"].get("scale"))
            + jnp.float32(settings.predictor.gamma)
            * halo_exchange.dequantize_rows(
                state["pstore"]["data"], state["pstore"].get("scale")))}
    if settings.pull_mode == "collective":
        return halo_exchange.shard_staleness_error(
            eps_store, push_reps, data["local_slots"],
            data["local_boundary"], shard_rows, mesh)
    return halo_exchange.staleness_error(
        eps_store, push_reps, data["local_slots"], data["local_boundary"])


def make_epoch_fn(cfg: GNNConfig, opt: Optimizer, settings: TrainSettings,
                  mesh=None) -> Callable:
    if settings.mode not in MODES:
        raise ValueError(settings.mode)
    if settings.pull_mode not in ("gather", "collective"):
        raise ValueError(settings.pull_mode)
    if settings.pull_mode == "collective" and mesh is None:
        raise ValueError("pull_mode='collective' needs the mesh")
    if settings.predictor.enabled and settings.mode != "digest":
        raise ValueError("the SAT predictor rides the stale store — "
                         f"mode must be 'digest', got {settings.mode!r}")
    loss_fn = make_subgraph_loss(cfg)

    def epoch_fn(state: dict, data: dict) -> tuple[dict, dict]:
        with jax.named_scope(INPUTS_SCOPE):
            r = state["epoch"] + 1        # 1-indexed, as in Algorithm 1
        x_global = data["x_global"]                         # (N+1, d)
        struct = data["struct"]
        halo_size = data["halo_ids"].shape[1]
        # Layer-0 halo features as device-local per-subgraph slabs
        # (M, H+1, d), row H the zero sentinel (x_global[N]).  The
        # partition baseline drops cross-subgraph information by zeroing
        # the halo *tables* (this slab; the stale slab below stays at its
        # zero init), NOT the ELL weights — GAT's attention denominator
        # and SAGE's mean still see the dropped neighbors as zero
        # vectors, matching the seed semantics exactly.
        with jax.named_scope(INPUTS_SCOPE):
            x_halo0 = x_global[data["halo_ids_x"]]          # (M, H+1, d)
            if settings.mode == "partition":
                x_halo0 = jnp.zeros_like(x_halo0)

        # GAT owner-shard dedup: the cache holds *projected* rows
        # (z{ell} = W·h̃, projected once per owner shard per layer at
        # pull time) instead of raw stale reps — see
        # project_store_tables.  The projection rides the staleness
        # contract the representations already have: frozen between
        # syncs at the pull-time W, and under the same stop_gradient as
        # the stale rows (the legacy path differentiated W through the
        # halo einsum; here that term is dropped with the rest of the
        # stale branch — pull epochs still see the identical forward,
        # and gat_halo_dedup=False restores the legacy semantics).
        use_projected = gat_projected(cfg)

        # The stale slab feeding this epoch's out-of-subgraph products —
        # device-local (M, L-1, H+1, hid) in storage precision: each
        # subgraph's slice holds only the halo rows it references, so
        # per-device residency scales with |halo(G_m)|, not |boundary|.
        if settings.mode == "propagation" and cfg.num_layers > 1:
            # Fresh exchange every epoch: exact reps at current params,
            # gathered down to the per-subgraph halo slabs.
            _, reps = full_graph_forward(cfg, state["params"], data, mesh)
            ids = jnp.clip(data["halo_ids_x"], 0, reps[0].shape[0] - 1)
            hv = jnp.pad(data["halo_valid"], ((0, 0), (0, 1)))
            if use_projected:
                # Fresh rows projected once over the full-graph table (N
                # rows per layer) rather than per-subgraph slabs.
                cache = {}
                for ell in range(cfg.num_layers - 1):
                    w = state["params"][f"layer_{ell + 1}"]["w"]
                    z = jnp.einsum("nd,dhk->nhk", reps[ell], w)
                    z = z.reshape(z.shape[0], -1)[ids]      # (M, H+1, w)
                    z = jnp.where(hv[:, :, None], z, 0.0)
                    q, sc = halo_exchange.quantize_rows(
                        z, settings.precision)
                    cache[f"z{ell}"] = q[:, None]
                    if sc is not None:
                        cache[f"z{ell}_scale"] = sc[:, None]
            else:
                slab = jnp.stack([rep[ids] for rep in reps], axis=1)
                slab = jnp.where(hv[:, None, :, None], slab, 0.0)
                q, sc = halo_exchange.quantize_rows(slab,
                                                    settings.precision)
                cache = ({"data": q} if sc is None
                         else {"data": q, "scale": sc})
            pcache = None
        elif settings.mode == "digest":
            cache, pcache = _digest_pull(cfg, settings, state, data,
                                         mesh, r)
        else:
            cache = state["cache"]
            pcache = None

        with jax.named_scope(INPUTS_SCOPE):
            x_local = x_global[data["local_ids"]]           # (M, S, d)
        n_hidden = cfg.num_layers - 1
        pred_tables = pcache is not None

        def sub_loss(params, x_loc, x_h0, cache_m, pcache_m, struct_m,
                     labels, mask):
            # Layer 0 gathers raw halo features from this subgraph's
            # feature slab; layers ℓ≥1 gather stale reps straight from its
            # pulled storage-precision slab — both via the fused
            # pull+aggregate path with the per-subgraph halo-slot ELL and
            # its precomputed chunk worklist.  Under GAT dedup the slab
            # rows are pre-projected (projected_halo_ref) so the layer
            # skips its per-subgraph W·h̃ einsum.
            wl = (struct_m.get("wl_ids"), struct_m.get("wl_cnt"))
            tables = [halo_ref(x_h0, None, struct_m["out_nbr"],
                               struct_m["out_wts"], *wl)]
            for ell in range(n_hidden):
                if use_projected:
                    zsc = cache_m.get(f"z{ell}_scale")
                    tables.append(projected_halo_ref(
                        cache_m[f"z{ell}"][0],
                        zsc[0] if zsc is not None else None,
                        struct_m["out_nbr"], struct_m["out_wts"]))
                else:
                    pk = {}
                    if pred_tables:
                        # Fused SAT epilogue: the kernel reads
                        # dequant(stale) + γ·dequant(delta) per row.
                        ptab, psc = halo_exchange.layer_table(pcache_m,
                                                              ell)
                        pk = dict(pdata=ptab, pscale=psc,
                                  gamma=settings.predictor.gamma)
                    tables.append(halo_ref(
                        *halo_exchange.layer_table(cache_m, ell),
                        struct_m["out_nbr"], struct_m["out_wts"], *wl,
                        **pk))
            return loss_fn(params, x_loc, tables, struct_m, labels, mask)

        vg = halo_exchange.per_subgraph(
            jax.value_and_grad(sub_loss, has_aux=True), mesh,
            in_axes=(None, 0, 0, 0, 0, 0, 0, 0), what="per-subgraph loss",
            check_vma=vma_checkable(cfg.backend))
        with jax.named_scope(LOSS_SCOPE):
            (losses, (push_reps, logits)), grads = vg(
                state["params"], x_local, x_halo0, cache, pcache, struct,
                data["labels"], data["train_mask"])
        params, opt_state, step = _opt_step(opt, state, grads)

        if settings.llcg_correction:
            # LLCG server correction: full-neighbor gradient on a sampled
            # node mini-batch, plain SGD on the server.
            key = jax.random.fold_in(jax.random.PRNGKey(17), r)
            sample = (jax.random.uniform(key, data["full_train_mask"][0]
                                         .shape)
                      < settings.correction_frac)
            corr_mask = data["full_train_mask"][0] & sample

            def server_loss(p):
                logits, _ = full_graph_forward(cfg, p, data, mesh)
                return softmax_cross_entropy(
                    logits, data["full_labels"][0],
                    corr_mask.astype(jnp.float32))

            corr_grads = jax.grad(server_loss)(params)
            params = jax.tree.map(
                lambda p, g: p - settings.correction_lr * g, params,
                corr_grads)

        (new_store, new_residual, eps, new_last, new_pstore,
         new_hist) = _digest_push(cfg, settings, state, data, push_reps,
                                  mesh, r)

        metrics = _epoch_metrics(losses, logits, data["labels"],
                                 data["train_mask"], eps, new_last, r)
        new_state = {"params": params, "opt_state": opt_state,
                     "store": new_store, "cache": cache,
                     "epoch": r, "step": step}
        if new_residual is not None:
            new_state["push_residual"] = new_residual
        if new_pstore is not None:
            new_state["pstore"] = new_pstore
            new_state["predictor"] = new_hist
        if pcache is not None:
            new_state["pcache"] = pcache
        if new_last is not None:
            new_state["push_ok"] = state["push_ok"]
            new_state["last_push_round"] = new_last
        return new_state, metrics

    return epoch_fn


OPT_SCOPE = "digest/opt"


@jax.named_scope(OPT_SCOPE)
def _opt_step(opt: Optimizer, state: dict, grads) -> tuple:
    """Global AGG (Algorithm 1 line 13), the uniform average of the
    per-subgraph gradients, then the optimizer's update: (params,
    opt_state, step)."""
    mean_grads = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
    params, opt_state = opt.update(mean_grads, state["opt_state"],
                                   state["params"], state["step"])
    return params, opt_state, state["step"] + 1


METRICS_SCOPE = "digest/metrics"


@jax.named_scope(METRICS_SCOPE)
def _epoch_metrics(losses, logits, labels, mask, eps, last_push, r) -> dict:
    """The epoch's reported metrics: mean loss, training micro-F1, the
    staleness probe's eps and, with fault state, the push age."""
    metrics = {"loss": jnp.mean(losses),
               "train_f1": micro_f1(logits, labels,
                                    mask.astype(jnp.float32)),
               "staleness_eps": eps}
    if last_push is not None:
        metrics["push_age"] = faults_mod.measured_staleness(last_push, r)
    return metrics


# ---------------------------------------------------------------------------
# State init + high-level training loop
# ---------------------------------------------------------------------------

def init_state(cfg: GNNConfig, opt: Optimizer, data: dict, seed: int = 0,
               precision: HaloPrecision = HaloPrecision(),
               predictor: PredictorConfig = PredictorConfig()) -> dict:
    check_worklist_geometry(cfg, data)
    params = init_params(jax.random.PRNGKey(seed), gnn_specs(cfg))
    num_slots = int(data["store_ids"].shape[0]) - 1
    l1 = max(cfg.num_layers - 1, 1)
    num_parts, s = data["local_ids"].shape
    halo_size = int(data["halo_ids"].shape[1])
    if gat_projected(cfg):
        # GAT dedup: the pulled cache holds per-layer *projected* slabs
        # z{ell} = W_{ell+1}·h̃ of width heads·head_dim (= the consuming
        # layer's dout), flat keys so the pytree stays one level deep for
        # shardings/checkpoints.  Leading (M, 1, H+1, ·) matches the
        # per-layer pull_slab/collective_pull output.
        cache = {}
        for ell in range(l1):
            w_ell = cfg.layer_dims[ell + 1][1]
            cache[f"z{ell}"] = jnp.zeros(
                (num_parts, 1, halo_size + 1, w_ell), precision.dtype)
            if precision.has_scale:
                cache[f"z{ell}_scale"] = jnp.ones(
                    (num_parts, 1, halo_size + 1, 1), jnp.float32)
    else:
        cache = halo_exchange.init_slab(num_parts, l1, halo_size,
                                        cfg.hidden_dim, precision)
    state = {
        "params": params,
        "opt_state": opt.init(params),
        # Authoritative owner-sharded compact store (O(|boundary|·L·d)
        # total, 1/M per device) + the device-local pulled halo slabs
        # (O(Σ_m |halo(G_m)|·L·d) total; the seed kept a replicated
        # O(M·H·L·d) fp32 cache).
        "store": halo_exchange.init_store(l1, num_slots, cfg.hidden_dim,
                                          precision),
        "cache": cache,
        "epoch": jnp.asarray(0, jnp.int32),
        "step": jnp.asarray(0, jnp.int32),
    }
    if precision.error_feedback:
        state["push_residual"] = jnp.zeros((num_parts, l1, s,
                                            cfg.hidden_dim), jnp.float32)
    if predictor.enabled and cfg.num_layers > 1:
        # SAT leaves (see repro.core.predictor): the pstore mirrors the
        # store's slot geometry/precision exactly, so every exchange
        # helper and the checkpoint layout apply verbatim; the history
        # rides the push buffers' shape.  The dedup GAT path folds the
        # prediction before projection and needs no pulled pcache slab.
        state["pstore"] = halo_exchange.init_store(
            l1, num_slots, cfg.hidden_dim, precision)
        state["predictor"] = predictor_mod.init_history(
            num_parts, l1, s, cfg.hidden_dim)
        if not gat_projected(cfg):
            state["pcache"] = halo_exchange.init_slab(
                num_parts, l1, halo_size, cfg.hidden_dim, precision)
    return state


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def evaluate(cfg: GNNConfig, params: Pytree, data: dict, mesh=None) -> dict:
    """Full-graph micro-F1 and loss per split; pass the training ``mesh``
    when ``params``/``data`` live on it."""
    logits, _ = full_graph_forward(cfg, params, data, mesh)
    out = {}
    for split in ("train", "val", "test"):
        mask = data[f"full_{split}_mask"][0].astype(jnp.float32)
        out[f"{split}_f1"] = micro_f1(logits, data["full_labels"][0], mask)
        out[f"{split}_loss"] = softmax_cross_entropy(
            logits, data["full_labels"][0], mask)
    return out


def digest_train(cfg: GNNConfig, opt: Optimizer, data: dict,
                 settings: TrainSettings, epochs: int,
                 eval_every: int = 10, seed: int = 0,
                 verbose: bool = False, mesh=None, faults=None,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                 resume: bool = False) -> tuple[dict, dict]:
    """Run training; returns (final_state, history dict of lists).

    ``mesh`` is required for ``pull_mode="collective"`` (the explicit
    shard_map pull/push paths — single- or multi-pod; the exchange
    auto-detects a "pod" axis); the default gather mode ignores it.

    ``faults`` (a :class:`repro.core.faults.FaultConfig` or
    ``FaultSchedule``) injects deterministic push faults through the
    per-shard ``push_ok`` mask — see ``_digest_push``; combined with
    ``settings.max_staleness`` the watchdog bounds the resulting
    staleness.  A ``None``/zero-rate schedule leaves the trajectory
    bitwise identical to a run without fault state.

    ``ckpt_dir`` + ``ckpt_every`` save an atomic, checksummed
    checkpoint of the full training state every ``ckpt_every`` epochs;
    ``resume=True`` restores the newest *valid* checkpoint (corrupt or
    partial ones are skipped) and continues to ``epochs`` — the epoch
    function is deterministic in its state, so a killed-and-resumed
    run finishes bitwise equal to an uninterrupted one (gcn/sage;
    gat ≤ 1e-6)."""
    if settings.pull_mode == "collective" and mesh is not None:
        check_collective_geometry(data, mesh)
    schedule = faults_mod.check_schedule(faults)
    num_parts = int(data["local_ids"].shape[0])
    fault_aware = (schedule is not None
                   or settings.max_staleness is not None)
    state = init_state(cfg, opt, data, seed=seed,
                       precision=settings.precision,
                       predictor=settings.predictor)
    if fault_aware:
        state = faults_mod.attach_fault_state(state, num_parts)
    start = 0
    if resume:
        if ckpt_dir is None:
            raise ValueError("resume=True needs ckpt_dir")
        step = ckpt_io.latest_step(ckpt_dir)
        if step is not None:
            state, _ = ckpt_io.restore_checkpoint(ckpt_dir, state,
                                                  step=step)
            start = int(np.asarray(state["epoch"]))
    epoch_fn = jax.jit(make_epoch_fn(cfg, opt, settings, mesh=mesh))
    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    hist: dict[str, list] = {"epoch": [], "loss": [], "train_f1": [],
                             "val_f1": [], "test_f1": [], "time": [],
                             "staleness_eps": []}
    if fault_aware:
        hist["push_age"] = []
    t0 = time.perf_counter()
    for e in range(start, epochs):
        if fault_aware:
            ok = (schedule.push_ok(e + 1, num_parts) if schedule is not None
                  else np.ones(num_parts, dtype=bool))
            state["push_ok"] = jnp.asarray(ok)
        state, m = epoch_fn(state, tdata)
        if (e + 1) % eval_every == 0 or e == epochs - 1:
            ev = evaluate(cfg, state["params"], tdata, mesh)
            hist["epoch"].append(e + 1)
            hist["loss"].append(float(m["loss"]))
            hist["train_f1"].append(float(m["train_f1"]))
            hist["val_f1"].append(float(ev["val_f1"]))
            hist["test_f1"].append(float(ev["test_f1"]))
            hist["staleness_eps"].append(
                np.asarray(m["staleness_eps"]).tolist())
            hist["time"].append(time.perf_counter() - t0)
            if fault_aware:
                hist["push_age"].append(int(m["push_age"]))
            if verbose:
                print(f"[{settings.mode}] epoch {e+1:4d} "
                      f"loss {float(m['loss']):.4f} "
                      f"val_f1 {float(ev['val_f1']):.4f}")
        if ckpt_dir and ckpt_every and (e + 1) % ckpt_every == 0:
            ckpt_io.save_checkpoint(ckpt_dir, e + 1, state)
    return state, hist


# ---------------------------------------------------------------------------
# Mini-batch sampled training (stale-store control variates)
# ---------------------------------------------------------------------------

def make_sampled_epoch_fn(cfg: GNNConfig, opt: Optimizer,
                          settings: TrainSettings, mesh=None) -> Callable:
    """Build the jitted sampled step ``(state, data, batch) -> (state,
    metrics)`` — the mini-batch regime over the SAME stale store.

    ``batch`` is one :class:`repro.graph.sampler.NeighborSampler` draw
    (``seed_mask``/``edge_scale``/``edge_keep``, jnp-converted).  Per
    step: in-subgraph sampled neighbors aggregate fresh, their complement
    reads the **control-variate history** — the device-local last-step
    representations (``state["hist"]``) for local rows, the pulled stale
    slab (refreshed by the unchanged ``_digest_pull`` at
    ``sync_interval`` cadence) for out-of-subgraph rows — and the loss is
    masked to the seed set.  PUSH, staleness probe and collective routing
    are byte-identical to the full-batch epoch (shared helpers), so the
    compiled-HLO census is unchanged: zero all-gathers, the same ragged
    all_to_all count per store tensor.

    ``settings.sample_estimator``: "cv" (VR-GCN) or "plain" — plain
    neighbor sampling is exactly the CV estimator against an all-zero
    history, so it is implemented by feeding zeros as the baseline (the
    variance benchmark's control).
    """
    if settings.mode != "digest":
        raise ValueError("sampled training rides the stale store — "
                         f"mode must be 'digest', got {settings.mode!r}")
    if settings.pull_mode not in ("gather", "collective"):
        raise ValueError(settings.pull_mode)
    if settings.pull_mode == "collective" and mesh is None:
        raise ValueError("pull_mode='collective' needs the mesh")
    if settings.sample_estimator not in ("cv", "plain"):
        raise ValueError(f"sample_estimator must be 'cv' or 'plain', "
                         f"got {settings.sample_estimator!r}")
    use_projected = gat_projected(cfg)
    n_hidden = cfg.num_layers - 1
    pred_tables = settings.predictor.enabled and not use_projected

    def sub_loss(params, x_loc, x_h0, cache_m, pcache_m, hist_m, struct_m,
                 labels, smask, escale, ekeep):
        # Same per-layer halo tables as the full-batch sub_loss; the
        # sampled forward additionally reads the local history rows.
        wl = (struct_m.get("wl_ids"), struct_m.get("wl_cnt"))
        tables = [halo_ref(x_h0, None, struct_m["out_nbr"],
                           struct_m["out_wts"], *wl)]
        for ell in range(n_hidden):
            if use_projected:
                zsc = cache_m.get(f"z{ell}_scale")
                tables.append(projected_halo_ref(
                    cache_m[f"z{ell}"][0],
                    zsc[0] if zsc is not None else None,
                    struct_m["out_nbr"], struct_m["out_wts"]))
            else:
                pk = {}
                if pred_tables and pcache_m is not None:
                    ptab, psc = halo_exchange.layer_table(pcache_m, ell)
                    pk = dict(pdata=ptab, pscale=psc,
                              gamma=settings.predictor.gamma)
                tables.append(halo_ref(
                    *halo_exchange.layer_table(cache_m, ell),
                    struct_m["out_nbr"], struct_m["out_wts"], *wl, **pk))
        tables = [jax.lax.stop_gradient(t) for t in tables]
        hist_tables = [jax.lax.stop_gradient(hist_m[i])
                       for i in range(n_hidden)]
        samp = {"edge_scale": escale, "edge_keep": ekeep}
        logits, push = gnn_forward_sampled(cfg, params, x_loc, tables,
                                           hist_tables, struct_m, samp)
        loss = softmax_cross_entropy(logits, labels, smask)
        return loss, (jnp.stack(push) if push else
                      jnp.zeros((0,) + x_loc.shape), logits)

    def step_fn(state: dict, data: dict, batch: dict) -> tuple[dict, dict]:
        x_global = data["x_global"]
        with jax.named_scope(INPUTS_SCOPE):
            r = state["epoch"] + 1
            x_halo0 = x_global[data["halo_ids_x"]]
        cache, pcache = _digest_pull(cfg, settings, state, data, mesh, r)
        with jax.named_scope(INPUTS_SCOPE):
            x_local = x_global[data["local_ids"]]
        if settings.sample_estimator == "cv":
            hist = state["hist"]
        else:
            hist = jnp.zeros_like(state["hist"])

        vg = halo_exchange.per_subgraph(
            jax.value_and_grad(sub_loss, has_aux=True), mesh,
            in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0),
            what="per-subgraph loss",
            check_vma=vma_checkable(cfg.backend))
        with jax.named_scope(LOSS_SCOPE):
            (losses, (push_reps, logits)), grads = vg(
                state["params"], x_local, x_halo0, cache, pcache, hist,
                data["struct"], data["labels"], batch["seed_mask"],
                batch["edge_scale"], batch["edge_keep"])
        params, opt_state, step = _opt_step(opt, state, grads)

        (new_store, new_residual, eps, new_last, new_pstore,
         new_hist) = _digest_push(cfg, settings, state, data, push_reps,
                                  mesh, r)

        metrics = _epoch_metrics(losses, logits, data["labels"],
                                 batch["seed_mask"], eps, new_last, r)
        # The CV history refreshes every step: the padded SPMD step
        # computes every local row's representation anyway, so the CV
        # baseline for in-subgraph rows is at most one step stale (the
        # halo side keeps the sync_interval staleness of the store).
        new_state = {"params": params, "opt_state": opt_state,
                     "store": new_store, "cache": cache,
                     "hist": push_reps if n_hidden > 0 else state["hist"],
                     "epoch": r, "step": step}
        if new_residual is not None:
            new_state["push_residual"] = new_residual
        if new_pstore is not None:
            new_state["pstore"] = new_pstore
            new_state["predictor"] = new_hist
        if pcache is not None:
            new_state["pcache"] = pcache
        if new_last is not None:
            new_state["push_ok"] = state["push_ok"]
            new_state["last_push_round"] = new_last
        return new_state, metrics

    return step_fn


def init_sampled_state(cfg: GNNConfig, opt: Optimizer, data: dict,
                       seed: int = 0,
                       precision: HaloPrecision = HaloPrecision(),
                       predictor: PredictorConfig = PredictorConfig()
                       ) -> dict:
    """:func:`init_state` + the device-local control-variate history
    ``hist`` (M, L-1, S, hidden) fp32 — each subgraph's own-row
    representations from the previous step, zero-initialized like the
    store (unused rows: the in-ELL's padding entries point at the zero
    sentinel, and their residual weights are zero anyway)."""
    state = init_state(cfg, opt, data, seed=seed, precision=precision,
                       predictor=predictor)
    num_parts, s = data["local_ids"].shape
    state["hist"] = jnp.zeros(
        (num_parts, cfg.num_layers - 1, s, cfg.hidden_dim), jnp.float32)
    return state


def sampled_train(cfg: GNNConfig, opt: Optimizer, data: dict, sampler,
                  settings: TrainSettings, steps: int, eval_every: int = 10,
                  seed: int = 0, verbose: bool = False, mesh=None,
                  faults=None, ckpt_dir: Optional[str] = None,
                  ckpt_every: int = 0, resume: bool = False
                  ) -> tuple[dict, dict]:
    """Run mini-batch sampled training; returns (final_state, history).

    ``sampler`` is a :class:`repro.graph.sampler.NeighborSampler`; step t
    consumes the deterministic ``sampler.sample(t)`` batch.  ``faults``
    and ``ckpt_dir``/``ckpt_every``/``resume`` behave exactly as in
    :func:`digest_train` — both the sampler and the fault schedule are
    pure functions of the step index, so a resumed run replays the
    identical batch and fault sequence."""
    if settings.pull_mode == "collective" and mesh is not None:
        check_collective_geometry(data, mesh)
    schedule = faults_mod.check_schedule(faults)
    num_parts = int(data["local_ids"].shape[0])
    fault_aware = (schedule is not None
                   or settings.max_staleness is not None)
    state = init_sampled_state(cfg, opt, data, seed=seed,
                               precision=settings.precision,
                               predictor=settings.predictor)
    if fault_aware:
        state = faults_mod.attach_fault_state(state, num_parts)
    start = 0
    if resume:
        if ckpt_dir is None:
            raise ValueError("resume=True needs ckpt_dir")
        step = ckpt_io.latest_step(ckpt_dir)
        if step is not None:
            state, _ = ckpt_io.restore_checkpoint(ckpt_dir, state,
                                                  step=step)
            start = int(np.asarray(state["epoch"]))
    step_fn = jax.jit(make_sampled_epoch_fn(cfg, opt, settings, mesh=mesh))
    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    hist: dict[str, list] = {"epoch": [], "loss": [], "train_f1": [],
                             "val_f1": [], "test_f1": [], "time": [],
                             "staleness_eps": []}
    if fault_aware:
        hist["push_age"] = []
    t0 = time.perf_counter()
    for t in range(start, steps):
        if fault_aware:
            ok = (schedule.push_ok(t + 1, num_parts) if schedule is not None
                  else np.ones(num_parts, dtype=bool))
            state["push_ok"] = jnp.asarray(ok)
        batch = {k: jnp.asarray(v) for k, v in sampler.sample(t).items()}
        state, m = step_fn(state, tdata, batch)
        if (t + 1) % eval_every == 0 or t == steps - 1:
            ev = evaluate(cfg, state["params"], tdata, mesh)
            hist["epoch"].append(t + 1)
            hist["loss"].append(float(m["loss"]))
            hist["train_f1"].append(float(m["train_f1"]))
            hist["val_f1"].append(float(ev["val_f1"]))
            hist["test_f1"].append(float(ev["test_f1"]))
            hist["staleness_eps"].append(
                np.asarray(m["staleness_eps"]).tolist())
            hist["time"].append(time.perf_counter() - t0)
            if fault_aware:
                hist["push_age"].append(int(m["push_age"]))
            if verbose:
                print(f"[sampled/{settings.sample_estimator}] "
                      f"step {t+1:4d} loss {float(m['loss']):.4f} "
                      f"val_f1 {float(ev['val_f1']):.4f}")
        if ckpt_dir and ckpt_every and (t + 1) % ckpt_every == 0:
            ckpt_io.save_checkpoint(ckpt_dir, t + 1, state)
    return state, hist
