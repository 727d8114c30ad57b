#!/usr/bin/env python
"""SPMD DIGEST GNN training launcher.

The DIGEST epoch function is written over stacked (M, ...) subgraph arrays;
under pjit we shard that leading M axis over the mesh "data" axis — one
subgraph per device slice, which *is* Algorithm 1's `for m in parallel`.
On CPU (1 device) the same program runs vmapped; on a fleet, identical code.

  PYTHONPATH=src python -m repro.launch.train_gnn --dataset flickr-sim \
      --parts 4 --epochs 40
"""
from __future__ import annotations

import argparse
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import HaloPrecision, HaloSpec, TrainSettings, evaluate, \
    init_state, make_epoch_fn, prepare_graph_data
from repro.graph import make_dataset
from repro.kernels.spmm import BACKENDS
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.gnn import GNNConfig
from repro.optim import adam


def subgraph_shardings(data: dict, state: dict, mesh) -> tuple[dict, dict]:
    """Shard every stacked (M, ...) array over the mesh's halo-exchange
    axes — the "data" axis alone, or the combined ("pod", "data") axes
    when the mesh carries a pod axis (the multi-pod production layout;
    device (p, d) then holds subgraph/shard block e = p·data + d).  The
    compact HaloExchange store is owner-sharded slot-wise (the
    partitioner groups slots by owning part, so each device holds
    exactly the boundary rows it pushes) and the pulled halo slabs
    (``state["cache"]``) are device-local, sharded over their leading
    subgraph axis — nothing about the stale state is replicated; pull
    epochs pay the §3.3 wire cost once.  Params/opt replicated (GNN
    weights are tiny)."""
    from repro.core.halo_exchange import exchange_axes

    axes = exchange_axes(mesh)
    mdim = axes if len(axes) > 1 else axes[0]
    rep = NamedSharding(mesh, P())
    m_shard = NamedSharding(mesh, P(mdim))
    slot_shard = NamedSharding(mesh, P(None, mdim, None))
    slab_shard = NamedSharding(mesh, P(mdim, None, None, None))

    data_sh = {}
    for k, v in data.items():
        if k.startswith("_"):
            continue
        if k in ("x_global", "store_ids") or k.startswith("full_"):
            data_sh[k] = jax.tree.map(lambda _: rep, v)
        elif k in ("pull_send", "pull_recv"):
            # PullPlan routing: leading axis is the owner/requester part.
            data_sh[k] = NamedSharding(mesh, P(mdim, None, None))
        elif k == "struct":
            data_sh[k] = {kk: m_shard for kk in v}
        else:
            data_sh[k] = m_shard
    state_sh = {
        "params": jax.tree.map(lambda _: rep, state["params"]),
        "opt_state": jax.tree.map(lambda _: rep, state["opt_state"]),
        "store": jax.tree.map(lambda _: slot_shard, state["store"]),
        "cache": jax.tree.map(lambda _: slab_shard, state["cache"]),
        "epoch": rep, "step": rep,
    }
    if "push_residual" in state:
        state_sh["push_residual"] = slab_shard
    if "pstore" in state:
        # SAT predictor leaves (repro.core.predictor): the pstore is
        # owner-sharded exactly like the store, the pulled pcache slab
        # device-local like the cache, and the push-side history rides
        # the push buffers' (M, ...) layout (count is per-part).
        state_sh["pstore"] = jax.tree.map(lambda _: slot_shard,
                                          state["pstore"])
        state_sh["predictor"] = {"prev": slab_shard, "ema": slab_shard,
                                 "coef": NamedSharding(mesh, P(mdim, None)),
                                 "count": m_shard}
        if "pcache" in state:
            state_sh["pcache"] = jax.tree.map(lambda _: slab_shard,
                                              state["pcache"])
    if "hist" in state:
        # Control-variate history (M, L-1, S, hidden): each device keeps
        # its own subgraphs' last-step representations — never exchanged.
        state_sh["hist"] = slab_shard
    if "push_ok" in state:
        # Fault-aware leaves (repro.core.faults.attach_fault_state):
        # per-shard (M,) push mask + last-push age table — sharded like
        # the subgraphs they gate.
        state_sh["push_ok"] = m_shard
        state_sh["last_push_round"] = m_shard
    return data_sh, state_sh


def model_config(g, data: dict, *, model: str, hidden_dim: int,
                 num_layers: int = 3, **knobs) -> GNNConfig:
    """The GNNConfig for graph ``g`` partitioned into ``data``: its
    feature and class widths.  ``knobs`` are the other GNNConfig fields
    (backend, ladder overrides)."""
    return GNNConfig(model=model, num_layers=num_layers,
                     in_dim=g.features.shape[1], hidden_dim=hidden_dim,
                     num_classes=int(g.labels.max()) + 1, **knobs)


def jit_epoch(cfg: GNNConfig, opt, settings: TrainSettings, data: dict,
              state: dict, mesh):
    """The full-batch DIGEST epoch, jitted over ``mesh`` with every
    input's sharding (:func:`subgraph_shardings`).  Returns ``(epoch_fn,
    state, tdata)``, the call ``epoch_fn(state, tdata)`` takes: ``tdata``
    is ``data`` without its host-only ``_`` keys, and both are placed on
    their shardings, so the first epoch's input types are the ones every
    later epoch passes back (one compile, not two)."""
    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    data_sh, state_sh = subgraph_shardings(tdata, state, mesh)
    epoch_fn = jax.jit(make_epoch_fn(cfg, opt, settings, mesh=mesh),
                       in_shardings=(state_sh, data_sh))
    return (epoch_fn, jax.device_put(state, state_sh),
            jax.device_put(tdata, data_sh))


def batch_shardings(mesh) -> dict:
    """Shardings for one sampler batch (``NeighborSampler.sample``):
    every array is stacked (M, ...) like the subgraph data, so it shards
    over the same halo-exchange axes — each device receives only its own
    subgraphs' seed masks and edge samples."""
    from repro.core.halo_exchange import exchange_axes

    axes = exchange_axes(mesh)
    mdim = axes if len(axes) > 1 else axes[0]
    m_shard = NamedSharding(mesh, P(mdim))
    return {k: m_shard for k in ("seed_mask", "edge_scale", "edge_keep")}


def _push_ok(schedule, rnd: int, num_parts: int):
    import jax.numpy as jnp
    import numpy as np
    ok = (schedule.push_ok(rnd, num_parts) if schedule is not None
          else np.ones(num_parts, dtype=bool))
    return jnp.asarray(ok)


def _maybe_resume(args, state) -> int:
    """Epoch/step to start from: the newest valid checkpoint's, or 0."""
    if not args.resume:
        return 0
    from repro.checkpoint import latest_step
    step = latest_step(args.ckpt_dir)
    if step is None:
        print(f"resume: no valid checkpoint in {args.ckpt_dir}, "
              f"starting fresh")
        return 0
    return int(step)


def _restore(args, state):
    from repro.checkpoint import restore_checkpoint
    state, step = restore_checkpoint(args.ckpt_dir, state)
    print(f"resume: restored step {step} from {args.ckpt_dir}")
    return state, step


def _maybe_ckpt(args, step: int, state) -> None:
    if args.ckpt_dir and args.ckpt_every and step % args.ckpt_every == 0:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt_dir, step, state)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="flickr-sim")
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--model", default="gcn")
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=40)
    ap.add_argument("--interval", type=int, default=10)
    ap.add_argument("--precision", default="fp32",
                    choices=("fp32", "bf16", "int8"),
                    help="HaloExchange wire/storage precision")
    ap.add_argument("--error-feedback", action="store_true",
                    help="accumulate int8/bf16 rounding residual at the "
                         "pusher (unbiased repeated pushes)")
    ap.add_argument("--pull", default="gather",
                    choices=("gather", "collective"),
                    help="PULL transport: dense gather (XLA all-gather "
                         "fallback; any device count) or the fully-SPMD "
                         "shard_map path — ragged all_to_all pulls plus "
                         "shard-local pushes (two-stage intra-pod + "
                         "inter-pod exchange when --pods > 1); needs "
                         "--parts to be a multiple of pods x data-axis "
                         "(k = parts/devices subgraphs and owner shards "
                         "per device)")
    ap.add_argument("--data-axis", type=int, default=1,
                    help="mesh data-axis size (1 on CPU)")
    ap.add_argument("--pods", type=int, default=1,
                    help="mesh pod-axis size; > 1 builds the multi-pod "
                         "('pod', 'data') mesh — collective mode then "
                         "runs the two-stage intra-pod all_to_all + "
                         "inter-pod ppermute exchange and needs --parts "
                         "to be a multiple of pods x data-axis")
    ap.add_argument("--halo-weight", type=float, default=0.0,
                    help="boundary-aware partitioning: weight of the "
                         "marginal-new-halo-rows term in the greedy "
                         "streaming score (0 = classic edge-cut LDG)")
    ap.add_argument("--order", default="none", choices=("none", "rcm"),
                    help="local-row layout: 'rcm' reorders each part's "
                         "rows by reverse Cuthill-McKee (halo slab runs "
                         "re-laid to match) so 128-row blocks reference "
                         "clustered slab chunks — lower worklist "
                         "occupancy, same math (pure row permutation)")
    ap.add_argument("--backend", default="auto", choices=BACKENDS,
                    help="aggregation kernel backend: 'auto' runs the "
                         "compiled Pallas kernels on a TPU and the jnp "
                         "reference elsewhere; 'jnp' forces the "
                         "reference, 'pallas*' a kernel, '*_interpret' "
                         "the kernel bodies in the Pallas interpreter — "
                         "the ladder knobs below act on the Pallas "
                         "paths")
    ap.add_argument("--stream-chunk-rows", type=int, default=None,
                    help="slab rows per chunk of the one-hot stream "
                         "kernels (default: kernel STREAM_CHUNK_ROWS; "
                         "also sets the precomputed worklist geometry)")
    ap.add_argument("--resident-max-bytes", type=int, default=None,
                    help="VMEM budget above which halo_spmm leaves the "
                         "resident kernel for the row gather (default: "
                         "kernel RESIDENT_STRIPE_MAX_BYTES)")
    ap.add_argument("--sampling", action="store_true",
                    help="mini-batch sampled training: fanout-bounded "
                         "neighbor sampling with stale-store control "
                         "variates (out-of-batch neighbors read the "
                         "HaloExchange store / local history as the "
                         "variance-reduction baseline); --epochs then "
                         "counts optimizer steps")
    ap.add_argument("--fanout", type=int, default=5,
                    help="sampled in-neighbors per row (rows with "
                         "deg <= fanout aggregate exactly)")
    ap.add_argument("--batch-seeds", type=int, default=512,
                    help="training seed rows per subgraph per step")
    ap.add_argument("--estimator", default="cv", choices=("cv", "plain"),
                    help="'cv' = VR-GCN control variates over the stale "
                         "store; 'plain' = scaled-sample-only neighbor "
                         "sampling (the variance-ablation control)")
    ap.add_argument("--predictor", default="none",
                    choices=("none", "delta", "ema"),
                    help="SAT staleness-alleviated prediction "
                         "(repro.core.predictor): serve dequant(store) "
                         "+ gamma*dequant(pstore) where the pstore "
                         "carries each row's last-sync delta ('delta') "
                         "or its beta-EMA ('ema'); 'none' compiles the "
                         "bitwise-identical predictor-free program")
    ap.add_argument("--predictor-gamma", type=float, default=1.0,
                    help="pull-time extrapolation coefficient gamma "
                         "(1.0 with 'delta' = linear extrapolation)")
    ap.add_argument("--predictor-beta", type=float, default=0.5,
                    help="EMA weight of the newest delta "
                         "(--predictor ema only)")
    ap.add_argument("--no-gat-dedup", action="store_true",
                    help="disable the GAT owner-shard projection dedup "
                         "(legacy per-subgraph halo projection)")
    ap.add_argument("--fault-crash-rate", type=float, default=0.0,
                    help="deterministic fault injection: per-(round, "
                         "worker) probability a shard's owner is inside "
                         "a crash window (its pushes are lost for "
                         "crash_rounds rounds; store keeps last-known-"
                         "good rows)")
    ap.add_argument("--fault-drop-rate", type=float, default=0.0,
                    help="probability a push round's wire transfer is "
                         "dropped for a shard")
    ap.add_argument("--fault-corrupt-rate", type=float, default=0.0,
                    help="probability a push payload is corrupted in "
                         "flight and CRC-rejected by the receiver "
                         "(observable effect = a drop)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the FaultSchedule (decisions are a "
                         "pure function of (seed, class, round, part) — "
                         "replayable)")
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="bounded-staleness watchdog: force-push any "
                         "shard whose last accepted push is this many "
                         "rounds old (Theorem-1/3 bound under faults)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="directory for atomic checksummed checkpoints "
                         "of the full training state")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N epochs/steps (0 = never)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest VALID checkpoint from "
                         "--ckpt-dir (partial/corrupt ones are skipped) "
                         "and continue to --epochs")
    args = ap.parse_args()
    if args.resume and not args.ckpt_dir:
        ap.error("--resume needs --ckpt-dir")
    enable_compile_cache()

    g = make_dataset(args.dataset, scale=args.scale)
    t_part = time.perf_counter()
    data = prepare_graph_data(g, args.parts, halo_weight=args.halo_weight,
                              stream_chunk_rows=args.stream_chunk_rows,
                              order=args.order)
    t_part = time.perf_counter() - t_part
    print(f"partition: {args.parts} parts, order={args.order}, "
          f"halo_weight={args.halo_weight} built in {t_part:.2f}s "
          f"({g.num_nodes} nodes, {len(g.indices) // 2} edges)")
    cfg = model_config(g, data, model=args.model, hidden_dim=64,
                       backend=args.backend,
                       stream_chunk_rows=args.stream_chunk_rows,
                       resident_max_bytes=args.resident_max_bytes,
                       gat_halo_dedup=not args.no_gat_dedup)
    opt = adam(5e-3)
    from repro.core import PredictorConfig
    predictor = PredictorConfig(kind=args.predictor,
                                gamma=args.predictor_gamma,
                                beta=args.predictor_beta)
    settings = TrainSettings(
        sync_interval=args.interval, mode="digest", pull_mode=args.pull,
        precision=HaloPrecision(args.precision,
                                error_feedback=args.error_feedback),
        sample_estimator=args.estimator,
        max_staleness=args.max_staleness,
        predictor=predictor)
    if predictor.enabled:
        print(f"predictor: kind={predictor.kind} gamma={predictor.gamma} "
              f"beta={predictor.beta}")
    from repro.core import faults as faults_mod
    schedule = faults_mod.check_schedule(faults_mod.FaultConfig(
        seed=args.fault_seed, crash_rate=args.fault_crash_rate,
        drop_push_rate=args.fault_drop_rate,
        corrupt_rate=args.fault_corrupt_rate))
    fault_aware = schedule is not None or args.max_staleness is not None
    if schedule is not None:
        print(f"faults: crash={args.fault_crash_rate} "
              f"drop={args.fault_drop_rate} "
              f"corrupt={args.fault_corrupt_rate} seed={args.fault_seed} "
              f"max_staleness={args.max_staleness}")
    mesh = make_host_mesh(data=args.data_axis, model=1, pod=args.pods)
    if args.pull == "collective":
        # Fail fast with the M-vs-mesh mismatch spelled out (the epoch
        # would raise the same error at trace time).  Counts every
        # exchange axis: pods x data on a multi-pod mesh.
        from repro.core import check_collective_geometry
        ppd = check_collective_geometry(data, mesh)
        print(f"collective mode: {ppd} subgraph(s)/owner shard(s) "
              f"per device over {dict(mesh.shape)}")

    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    sp = data["_sp"]
    spec = HaloSpec.from_partitions(sp, cfg.hidden_dim, cfg.num_layers,
                                    settings.precision)
    if args.sampling:
        from repro.core import init_sampled_state, make_sampled_epoch_fn
        from repro.graph import build_sampler

        sampler = build_sampler(data, args.fanout, args.batch_seeds)
        print(f"sampling: fanout={args.fanout} (max in-degree "
              f"{sampler.max_in_degree}), batch_seeds={args.batch_seeds}, "
              f"estimator={args.estimator}")
        state = init_sampled_state(cfg, opt, data,
                                   precision=settings.precision,
                                   predictor=settings.predictor)
        if fault_aware:
            state = faults_mod.attach_fault_state(state, args.parts)
        start = _maybe_resume(args, state)
        if start:
            state, _ = _restore(args, state)
        data_sh, state_sh = subgraph_shardings(tdata, state, mesh)
        step_fn = jax.jit(
            make_sampled_epoch_fn(cfg, opt, settings, mesh=mesh),
            in_shardings=(state_sh, data_sh, batch_shardings(mesh)))
        t0 = time.perf_counter()
        m = {"loss": float("nan")}
        for t in range(start, args.epochs):
            if fault_aware:
                state["push_ok"] = _push_ok(schedule, t + 1, args.parts)
            batch = {k: jax.numpy.asarray(v)
                     for k, v in sampler.sample(t).items()}
            state, m = step_fn(state, tdata, batch)
            _maybe_ckpt(args, t + 1, state)
        ev = evaluate(cfg, state["params"], tdata, mesh)
    else:
        state = init_state(cfg, opt, data, precision=settings.precision,
                           predictor=settings.predictor)
        if fault_aware:
            state = faults_mod.attach_fault_state(state, args.parts)
        start = _maybe_resume(args, state)
        if start:
            state, _ = _restore(args, state)
        epoch_fn, state, tdata = jit_epoch(cfg, opt, settings, data,
                                           state, mesh)
        t0 = time.perf_counter()
        m = {"loss": float("nan")}
        for e in range(start, args.epochs):
            if fault_aware:
                state["push_ok"] = _push_ok(schedule, e + 1, args.parts)
            state, m = epoch_fn(state, tdata)
            _maybe_ckpt(args, e + 1, state)
        ev = evaluate(cfg, state["params"], tdata, mesh)
    if fault_aware and "last_push_round" in state:
        import numpy as np
        age = int(state["epoch"]) - np.asarray(state["last_push_round"])
        print(f"fault staleness: max push age {int(age.max())} round(s) "
              f"(bound {args.max_staleness})")
    sync = spec.comm_bytes(sp.pull_rows(), sp.push_rows())
    wl = data["_worklist"]
    print(f"mesh={dict(mesh.shape)} epochs={args.epochs} "
          f"loss={float(m['loss']):.4f} val_f1={float(ev['val_f1']):.4f} "
          f"({(time.perf_counter()-t0)/args.epochs:.3f}s/epoch)")
    print(f"halo worklist: {wl.visited_chunks}/{wl.total_pairs} "
          f"(row-block x chunk) pairs occupied "
          f"({100 * wl.occupancy:.1f}%; chunk_rows={wl.chunk_rows})")
    print(f"store: {spec.store_nbytes()/1e6:.2f} MB total, "
          f"{spec.shard_nbytes()/1e6:.2f} MB/device; pull/sync: "
          f"sharded {sync['pull_bytes']/1e6:.2f} MB vs replicated "
          f"{spec.replicated_pull_nbytes()/1e6:.2f} MB")


if __name__ == "__main__":
    main()
