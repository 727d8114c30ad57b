#!/usr/bin/env python
"""End-to-end DIGEST GNN training driver (the paper's experiment):
dataset build → METIS-style partition → DIGEST training with periodic
stale sync → eval + checkpointing + communication accounting.

  PYTHONPATH=src python examples/train_digest_gnn.py \
      --dataset products-sim --parts 8 --epochs 200 --interval 10

Collective mode
---------------
``--pull`` selects the PULL/PUSH transport of the halo store:

  * ``gather`` (default): dense gather/scatter; XLA's SPMD partitioner
    inserts an all-gather of the owner-sharded slab under pjit.  Correct
    on any device count — the fallback when ``--parts`` does not divide
    the mesh data axis.
  * ``collective``: the fully-SPMD ``shard_map`` epoch.  PULL is one
    ragged ``all_to_all`` shipping only the slots each subgraph's halo
    references (per the PullPlan); PUSH and the Theorem-1 staleness
    probe run with owner-local offsets inside each device's own shards.
    Needs ``--parts`` to be a *multiple* of ``--data-axis``: each device
    then carries k = parts/data-axis subgraphs and owner shards
    (parts-per-device > 1 is the M-exceeds-pod-size regime; a
    non-multiple raises a spelled-out ValueError).

HLO guarantees (regression-tested in tests/test_hlo_collectives.py):
the compiled collective-mode epoch contains exactly one all-to-all per
store tensor (layers batched inside) and **zero** all-gather /
collective-permute / reduce-scatter ops — pushes provably never cross
devices, so §3.3's owner-local cost model is a property of the emitted
program, not a partitioner heuristic.
"""
import argparse
import json
import os

from repro.checkpoint import save_checkpoint
from repro.core import (HaloSpec, TrainSettings, digest_train,
                        epoch_comm_bytes, prepare_graph_data)
from repro.graph import make_dataset, partition_report
from repro.models.gnn import GNNConfig, gnn_specs
from repro.nn import param_count
from repro.optim import adam


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="products-sim")
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--model", default="gcn", choices=["gcn", "gat",
                                                       "sage"])
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=200)
    ap.add_argument("--interval", type=int, default=10)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--pull", default="gather",
                    choices=("gather", "collective"),
                    help="halo PULL/PUSH transport (see module "
                         "docstring); collective needs --parts to be a "
                         "multiple of --data-axis")
    ap.add_argument("--data-axis", type=int, default=1,
                    help="mesh data-axis size for --pull collective "
                         "(1 on a single-device host)")
    ap.add_argument("--pods", type=int, default=1,
                    help="mesh pod-axis size for --pull collective; "
                         "> 1 runs the two-stage multi-pod exchange "
                         "(--parts must be a multiple of pods x "
                         "data-axis)")
    ap.add_argument("--halo-weight", type=float, default=0.0,
                    help="boundary-aware partitioning score weight "
                         "(0 = classic edge-cut LDG)")
    ap.add_argument("--no-gat-dedup", action="store_true",
                    help="disable the GAT owner-shard projection dedup")
    ap.add_argument("--ckpt-dir", default="/tmp/digest_ckpt")
    args = ap.parse_args()

    g = make_dataset(args.dataset, scale=args.scale)
    data = prepare_graph_data(g, args.parts, halo_weight=args.halo_weight)
    cfg = GNNConfig(model=args.model,
                    num_layers=3 if args.model != "gat" else 2,
                    in_dim=g.features.shape[1], hidden_dim=args.hidden,
                    num_classes=int(g.labels.max()) + 1, heads=4,
                    gat_halo_dedup=not args.no_gat_dedup)
    pc = param_count(gnn_specs(cfg))
    print(f"dataset={g.name} nodes={g.num_nodes} edges={g.num_edges} "
          f"parts={args.parts} params={pc:,}")
    print(f"halo ratio per part: {data['_sp'].halo_ratio().round(2)}")
    quality = partition_report(g, data["_sp"])
    print(f"partition: edge_cut={quality['edge_cut']} "
          f"halo_rows={quality['halo_rows']} "
          f"boundary={quality['boundary']} "
          f"balance={quality['balance']:.3f}")
    spec = HaloSpec.from_partitions(data["_sp"], args.hidden,
                                    cfg.num_layers)
    print(f"halo store: {spec.store_nbytes()/1e6:.2f} MB total, "
          f"{spec.shard_nbytes()/1e6:.2f} MB/device (owner-sharded)")

    mesh = None
    if args.pull == "collective":
        from repro.core import check_collective_geometry
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(data=args.data_axis, pod=args.pods)
        ppd = check_collective_geometry(data, mesh)
        print(f"collective mode: {ppd} subgraph(s) per device over "
              f"{dict(mesh.shape)}")
    state, hist = digest_train(
        cfg, adam(args.lr), data,
        TrainSettings(sync_interval=args.interval, mode="digest",
                      pull_mode=args.pull),
        epochs=args.epochs, eval_every=max(args.epochs // 10, 1),
        verbose=True, mesh=mesh)

    comm = epoch_comm_bytes("digest", data["_sp"], g, pc, args.hidden,
                            cfg.num_layers, args.interval)
    comm_prop = epoch_comm_bytes("propagation", data["_sp"], g, pc,
                                 args.hidden, cfg.num_layers)
    print(f"\nfinal: loss={hist['loss'][-1]:.4f} "
          f"val_f1={hist['val_f1'][-1]:.4f} "
          f"test_f1={hist['test_f1'][-1]:.4f}")
    print(f"comm/epoch: digest={comm/1e6:.2f} MB vs "
          f"propagation={comm_prop/1e6:.2f} MB "
          f"({comm_prop/comm:.1f}x reduction)")
    path = save_checkpoint(args.ckpt_dir, args.epochs,
                           {"params": state["params"]})
    print(f"checkpoint: {path}")
    with open(os.path.join(args.ckpt_dir, "history.json"), "w") as f:
        json.dump(hist, f)


if __name__ == "__main__":
    main()
