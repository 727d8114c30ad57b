"""GNN dry-run: DIGEST's own workload (Algorithm 1) lowered on the
production mesh — M = k·256 subgraphs of a large synthetic graph, k per
chip on the "data" axis (``--parts-per-device``), compact HaloExchange
store sharded slot-wise.  ``--pull collective`` lowers the fully-SPMD
shard_map epoch instead of the partitioner-dependent gather/scatter
fallback: the ragged all_to_all pull on the single-pod 16x16 mesh, the
two-stage intra-pod all_to_all + inter-pod ppermute exchange over the
("pod", "data") axes on the multi-pod 2x16x16 one (``--multi-pod`` /
``--pods``), shard-local pushes on both — the lowered 512-chip program
must carry ZERO all-gathers (the CI dry-run smoke asserts it from this
script's census output).

  PYTHONPATH=src python -m repro.launch.dryrun_gnn [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun_gnn --multi-pod \\
      --pull collective --parts-per-device 2

Run as its own process (512 placeholder devices on the CPU platform,
which it pins itself, so it never takes an attached TPU).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (HaloPrecision, PredictorConfig, TrainSettings,
                        make_epoch_fn)
from repro.kernels.spmm import BACKENDS
from repro.launch.dryrun import collective_bytes, cost_properties
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS,
                               make_production_mesh)
from repro.models.gnn import GNNConfig, gnn_specs
from repro.nn import abstract_params
from repro.optim import adam


def abstract_gnn_case(num_nodes: int, num_parts: int, feat: int,
                      hidden: int, classes: int, deg_in: int, deg_out: int,
                      halo_frac: float, boundary_frac: float = 0.5,
                      chunk_rows: int = 512):
    """ShapeDtypeStruct stand-ins for a partitioned graph (no host build —
    at 256 parts × 1M nodes the partitioner would dominate; shapes are what
    the compiler needs).  ``boundary_frac`` models |boundary| / N — the
    compact HaloExchange store holds only those rows."""
    S = num_nodes // num_parts
    H = int(S * halo_frac)
    i32 = jnp.int32
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    # Node tables carry the sentinel row; pad row count to shard evenly.
    rows = ((num_nodes + 1 + num_parts - 1) // num_parts) * num_parts
    # Owner-sharded compact store: shard_rows rows per owner (incl. the
    # per-owner sentinel), R = num_parts · shard_rows total.
    shard_rows = ((int(num_nodes * boundary_frac) // num_parts + 1 + 7)
                  // 8) * 8
    slots = num_parts * shard_rows
    # Ragged pull-plan width: halo spread uniformly over owners.
    K = max((H + num_parts - 1) // num_parts, 1)
    # Chunk worklist of the out-ELL vs the (H+1)-row slab: 128-row output
    # blocks, worst-case static width = every chunk occupied.
    n_blocks = max(-(-S // 128), 1)
    n_chunks = max(-(-(H + 1) // chunk_rows), 1)
    data = {
        "x_global": sds((rows, feat), f32),
        "struct": {"in_nbr": sds((num_parts, S, deg_in), i32),
                   "in_wts": sds((num_parts, S, deg_in), f32),
                   "out_nbr": sds((num_parts, S, deg_out), i32),
                   "out_wts": sds((num_parts, S, deg_out), f32),
                   "wl_ids": sds((num_parts, n_blocks, n_chunks), i32),
                   "wl_cnt": sds((num_parts, n_blocks), i32)},
        "local_ids": sds((num_parts, S), i32),
        "local_valid": sds((num_parts, S), jnp.bool_),
        "halo_ids": sds((num_parts, H), i32),
        "halo_valid": sds((num_parts, H), jnp.bool_),
        "halo_ids_x": sds((num_parts, H + 1), i32),
        "local_slots": sds((num_parts, S), i32),
        "local_boundary": sds((num_parts, S), jnp.bool_),
        "halo_slots": sds((num_parts, H), i32),
        "store_ids": sds((slots,), i32),
        "sentinel_slots": sds((num_parts,), i32),
        "pull_send": sds((num_parts, num_parts, K), i32),
        "pull_recv": sds((num_parts, num_parts, K), i32),
        "labels": sds((num_parts, S), i32),
        "train_mask": sds((num_parts, S), jnp.bool_),
        "val_mask": sds((num_parts, S), jnp.bool_),
        "test_mask": sds((num_parts, S), jnp.bool_),
        # full-graph view (eval only; not used by the epoch fn)
        "full_struct": {"in_nbr": sds((1, 8, 1), i32),
                        "in_wts": sds((1, 8, 1), f32),
                        "out_nbr": sds((1, 8, 1), i32),
                        "out_wts": sds((1, 8, 1), f32)},
        "full_ids": sds((1, 8), i32),
        "full_valid": sds((1, 8), jnp.bool_),
        "full_labels": sds((1, 8), i32),
        "full_train_mask": sds((1, 8), jnp.bool_),
        "full_val_mask": sds((1, 8), jnp.bool_),
        "full_test_mask": sds((1, 8), jnp.bool_),
    }
    return data, S, H, rows, slots


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--nodes", type=int, default=1_048_576)
    ap.add_argument("--feat", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--deg", type=int, default=16)
    ap.add_argument("--precision", default="fp32",
                    choices=("fp32", "bf16", "int8"))
    ap.add_argument("--pull", default="gather",
                    choices=("gather", "collective"),
                    help="collective = fully-SPMD shard_map epoch: "
                         "ragged all_to_all pull + shard-local push on "
                         "a single pod; with --multi-pod/--pods the "
                         "two-stage intra-pod all_to_all + inter-pod "
                         "ppermute exchange over ('pod', 'data')")
    ap.add_argument("--pods", type=int, default=None,
                    help="pod-axis size of the production mesh "
                         "(default: 2 with --multi-pod, else 1; the "
                         "forced host platform has 512 devices, so "
                         "pods x 256 must fit)")
    ap.add_argument("--parts-per-device", type=int, default=1,
                    help="k subgraphs/owner shards per 'data' device "
                         "(M = k x data axis; the M > pod-size regime)")
    ap.add_argument("--backend", default="auto", choices=BACKENDS,
                    help="aggregation kernel backend the epoch lowers "
                         "with; the forced-host-device dry run compiles "
                         "for CPU, where 'auto' resolves to the jnp "
                         "reference — the Pallas kernels' own compile "
                         "check is tests/test_tpu_compile.py")
    ap.add_argument("--stream-chunk-rows", type=int, default=512,
                    help="slab rows per streamed halo_spmm chunk (also "
                         "the abstract worklist geometry)")
    ap.add_argument("--resident-max-bytes", type=int, default=None,
                    help="VMEM budget above which halo_spmm leaves "
                         "the resident kernel for the row gather "
                         "(default: kernel RESIDENT_STRIPE_MAX_BYTES; "
                         "Pallas backends only)")
    ap.add_argument("--predictor", default="none",
                    choices=("none", "delta", "ema"),
                    help="SAT staleness predictor kind: history pstore "
                         "rides the store sharding, prediction fuses "
                         "into the pull/dequant epilogue (adds exactly "
                         "one all_to_all per history tensor in the "
                         "collective census; 'none' lowers the identical "
                         "program as before)")
    ap.add_argument("--predictor-gamma", type=float, default=1.0)
    ap.add_argument("--predictor-beta", type=float, default=0.5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    mesh = make_production_mesh(multi_pod=args.multi_pod, pods=args.pods)
    data_axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    num_parts = args.parts_per_device
    for a in data_axes:
        num_parts *= mesh.shape[a]

    cfg = GNNConfig(model="gcn", num_layers=3, in_dim=args.feat,
                    hidden_dim=args.hidden, num_classes=64,
                    backend=args.backend,
                    stream_chunk_rows=args.stream_chunk_rows,
                    resident_max_bytes=args.resident_max_bytes)
    opt = adam(5e-3)
    precision = HaloPrecision(args.precision)
    pcfg = PredictorConfig(kind=args.predictor, gamma=args.predictor_gamma,
                           beta=args.predictor_beta)
    settings = TrainSettings(sync_interval=10, mode="digest",
                             pull_mode=args.pull, precision=precision,
                             predictor=pcfg)
    # (No M-vs-mesh geometry check needed here: num_parts is derived
    # from the mesh exchange axes above, so it divides by construction —
    # unlike train_gnn/examples, where --parts is user-supplied.)
    data, S, H, rows, slots = abstract_gnn_case(
        args.nodes, num_parts, args.feat, args.hidden, 64, args.deg,
        args.deg // 2, halo_frac=1.0, chunk_rows=args.stream_chunk_rows)

    rep = NamedSharding(mesh, P())
    mdim = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    m_shard = NamedSharding(mesh, P(mdim))
    node_shard = NamedSharding(mesh, P(mdim))

    specs = gnn_specs(cfg)
    params_abs = abstract_params(specs)
    # Owner-sharded HaloExchange store (L-1, M·shard_rows, hidden) in
    # storage precision (int8 adds the per-row scale column): each device
    # keeps only the shard it pushes.  The pulled cache is the device-
    # local per-subgraph halo slab (M, L-1, H+1, hidden).
    l1 = cfg.num_layers - 1
    H = data["halo_ids"].shape[1]
    store_abs = {"data": jax.ShapeDtypeStruct(
        (l1, slots, args.hidden), precision.dtype)}
    store_sh = {"data": NamedSharding(mesh, P(None, mdim, None))}
    cache_abs = {"data": jax.ShapeDtypeStruct(
        (num_parts, l1, H + 1, args.hidden), precision.dtype)}
    cache_sh = {"data": NamedSharding(mesh, P(mdim, None, None, None))}
    if precision.has_scale:
        store_abs["scale"] = jax.ShapeDtypeStruct(
            (l1, slots, 1), jnp.float32)
        store_sh["scale"] = NamedSharding(mesh, P(None, mdim, None))
        cache_abs["scale"] = jax.ShapeDtypeStruct(
            (num_parts, l1, H + 1, 1), jnp.float32)
        cache_sh["scale"] = NamedSharding(mesh, P(mdim, None, None, None))
    state_abs = {
        "params": params_abs,
        "opt_state": jax.eval_shape(opt.init, params_abs),
        "store": store_abs,
        "cache": cache_abs,
        "epoch": jax.ShapeDtypeStruct((), jnp.int32),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    state_sh = {
        "params": jax.tree.map(lambda _: rep, params_abs),
        "opt_state": jax.tree.map(lambda _: rep,
                                  state_abs["opt_state"]),
        "store": store_sh, "cache": cache_sh,
        "epoch": rep, "step": rep,
    }
    if pcfg.enabled:
        # SAT history rides the exact store / slab geometry: the pstore
        # is a second owner-sharded table, the raw-rep history is a
        # device-local per-subgraph slab, the pulled pcache mirrors the
        # halo cache (gcn dry-run model — not gat-projected).
        state_abs["pstore"] = dict(store_abs)
        state_sh["pstore"] = dict(store_sh)
        slab = jax.ShapeDtypeStruct((num_parts, l1, S, args.hidden),
                                    jnp.float32)
        slab_sh = NamedSharding(mesh, P(mdim, None, None, None))
        state_abs["predictor"] = {
            "prev": slab, "ema": slab,
            "coef": jax.ShapeDtypeStruct((num_parts, l1), jnp.float32),
            "count": jax.ShapeDtypeStruct((num_parts,), jnp.int32)}
        state_sh["predictor"] = {
            "prev": slab_sh, "ema": slab_sh,
            "coef": NamedSharding(mesh, P(mdim, None)),
            "count": m_shard}
        state_abs["pcache"] = dict(cache_abs)
        state_sh["pcache"] = dict(cache_sh)
    data_sh = {}
    for k, v in data.items():
        if k == "x_global":
            # Feature-table rows shard over "data" ONLY — one replica
            # per pod, sharded within it (same per-device residency as
            # the single-pod layout).  Sharding rows over the combined
            # ("pod", "data") axes makes XLA partition the layer-0
            # x_global[ids] gathers with inter-pod index all-gathers;
            # per-pod replication keeps those gathers intra-pod and the
            # compiled epoch all-gather-free (the CI census gate).
            data_sh[k] = NamedSharding(mesh, P("data", None))
        elif k == "store_ids":
            data_sh[k] = rep
        elif k in ("pull_send", "pull_recv"):
            data_sh[k] = NamedSharding(mesh, P(mdim, None, None))
        elif k == "struct":
            data_sh[k] = {kk: m_shard for kk in v}
        elif k.startswith("full_"):
            data_sh[k] = jax.tree.map(lambda _: rep, v)
        else:
            data_sh[k] = m_shard

    epoch_fn = make_epoch_fn(
        cfg, opt, settings,
        mesh=mesh if args.pull == "collective" else None)
    t0 = time.perf_counter()
    lowered = jax.jit(epoch_fn, in_shardings=(state_sh, data_sh)).lower(
        state_abs, data)
    compiled = lowered.compile()
    cost = cost_properties(compiled)
    mem = compiled.memory_analysis()
    # Census on the partitioned HLO: per-op byte totals AND op counts
    # (the CI dry-run smoke asserts all-gather == 0 from this JSON);
    # with a pod axis, replica-group analysis splits intra- vs
    # inter-pod bytes (device ids [0, data·model) are pod 0).
    pods = int(mesh.shape.get("pod", 1))
    # Devices per pod from the MESH shape (data·model), not the forced
    # host device count — logical ids [0, data·model) are pod 0
    # regardless of how many placeholder devices the platform exposes.
    pod_boundary = (int(mesh.shape["data"] * mesh.shape["model"])
                    if pods > 1 else 0)
    coll = collective_bytes(compiled.as_text(), pod_boundary)
    out = {
        "case": "digest_gnn_epoch",
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "nodes": args.nodes, "parts": num_parts, "S": S, "H": H,
        "hidden": args.hidden, "precision": args.precision,
        "pull_mode": args.pull, "parts_per_device": args.parts_per_device,
        "store_slots": slots, "shard_rows": slots // num_parts,
        "stream_chunk_rows": args.stream_chunk_rows,
        "predictor": args.predictor,
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll["total"],
        "collective_per_op": coll["per_op"],
        "collective_counts": coll["counts"],
        "collective_inter_pod_bytes": coll["inter_pod"],
        "compute_term_s": float(cost.get("flops", 0.0)) / PEAK_FLOPS,
        "memory_term_s": float(cost.get("bytes accessed", 0.0)) / HBM_BW,
        "collective_term_s": coll["total"] / ICI_BW,
        "t_compile_s": round(time.perf_counter() - t0, 2),
    }
    if mem is not None:
        out["mem_temp_gb"] = round(mem.temp_size_in_bytes / 1e9, 3)
        out["mem_arg_gb"] = round(mem.argument_size_in_bytes / 1e9, 3)
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
