#!/usr/bin/env python
"""Online GNN embedding-serving launcher.

Stands up the DIGEST serving path end to end: build + partition the
graph, refresh the all-node owner-sharded serving store from the
top-layer representations, then drive a Zipf query stream through the
jitted batched query engine (``repro.core.serving``) behind the hot-row
cache, reporting p50/p99 latency, queries/sec and cache hit-rate.

  PYTHONPATH=src python -m repro.launch.serve_gnn --dataset flickr-sim \
      --scale 0.5 --parts 8 --model gcn --batch 256 --cache-rows 2048

``--sharded`` additionally compiles the SPMD engine over the host mesh
(store sharded slot-wise, halo rows through the ragged collective pull)
and times per-part local-row batches — the multi-device deployment
shape.  Weights are randomly initialized: serving cost is independent
of training state; point ``--refreshes`` at >1 to also measure the
donation-friendly in-place store refresh.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import serving
from repro.core.digest import prepare_graph_data, top_layer_reps
from repro.graph import make_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.serving_driver import run_serve_loop
from repro.models.gnn import GNNConfig, gnn_specs
from repro.nn import init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="flickr-sim")
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--model", default="gcn",
                    choices=("gcn", "sage", "gat"))
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--batches", type=int, default=64)
    ap.add_argument("--warmup", type=int, default=4)
    ap.add_argument("--skew", type=float, default=1.1,
                    help="Zipf exponent of the query stream")
    ap.add_argument("--cache-rows", type=int, default=0,
                    help="hot-row cache capacity (0 disables)")
    ap.add_argument("--cache-ways", type=int, default=4)
    ap.add_argument("--storage", default="fp32",
                    choices=("fp32", "bf16", "int8"))
    ap.add_argument("--refreshes", type=int, default=1,
                    help="store refreshes to run (donated in-place)")
    ap.add_argument("--sharded", action="store_true",
                    help="also time the SPMD engine over the host mesh")
    args = ap.parse_args()
    enable_compile_cache()

    g = make_dataset(args.dataset, scale=args.scale, seed=0)
    data = prepare_graph_data(g, args.parts, seed=0)
    cfg = GNNConfig(model=args.model, num_layers=args.layers,
                    in_dim=g.features.shape[1], hidden_dim=args.hidden,
                    num_classes=int(g.labels.max()) + 1)
    params = init_params(jax.random.PRNGKey(0), gnn_specs(cfg))

    plan = serving.build_serve_plan(data)
    scfg = serving.ServeConfig(batch_size=args.batch,
                               cache_rows=args.cache_rows,
                               cache_ways=args.cache_ways,
                               storage=args.storage)
    store = serving.init_serve_store(plan, cfg.hidden_dim, scfg.precision)
    refresh = serving.make_refresh_fn()
    rdata = plan.refresh_data()
    reps = top_layer_reps(cfg, params, data)
    for _ in range(max(args.refreshes, 1)):
        store = refresh(store, reps, rdata)
    print(f"store: {plan.store_rows} slots x{cfg.hidden_dim} "
          f"({args.storage}), {args.parts} shards, "
          f"version {int(store['version'])}")

    # Zipf traffic, hubs hottest (popularity rank = descending degree).
    hot = np.argsort(-g.degrees()).astype(np.int32)
    queries = serving.zipf_queries(g.num_nodes, args.batch, args.batches,
                                   args.skew, seed=1, hot_ids=hot)
    qdata = plan.query_data()
    cache = serving.init_cache(scfg, cfg.num_classes)

    def step(cache, q):
        logits, cache = serving.serve_query(cfg, scfg, params, store,
                                            cache, qdata, jnp.asarray(q))
        return cache, logits

    cache, _, stats = run_serve_loop(step, queries, carry=cache,
                                     warmup=args.warmup,
                                     items_per_call=args.batch)
    print(f"query[{args.model}] batch={args.batch} skew={args.skew}: "
          f"p50 {stats.p50_ms:.2f} ms  p99 {stats.p99_ms:.2f} ms  "
          f"{stats.per_sec:,.0f} q/s  "
          f"cache hit-rate {serving.hit_rate(cache):.3f} "
          f"({args.cache_rows} rows, {args.cache_ways}-way)")

    if args.sharded:
        mesh = make_host_mesh(data=jax.device_count())
        sdata = plan.sharded_data(data)
        store_sh, sdata_sh, q_sh = serving.serve_shardings(store, sdata,
                                                           mesh)
        store_p = jax.device_put(store, store_sh)
        sdata_p = jax.tree.map(jax.device_put, sdata, sdata_sh)
        rng = np.random.default_rng(2)
        rows = rng.integers(0, plan.part_rows,
                            (args.batches, args.parts, args.batch))

        def sstep(carry, q_rows):
            out = serving.serve_query_sharded(
                cfg, scfg, mesh, plan.halo_size, params, store_p, sdata_p,
                jax.device_put(jnp.asarray(q_rows, jnp.int32), q_sh))
            return carry, out

        _, _, sstats = run_serve_loop(
            sstep, rows, warmup=args.warmup,
            items_per_call=args.parts * args.batch)
        print(f"sharded[{jax.device_count()} dev] "
              f"{args.parts}x{args.batch} rows/call: "
              f"p50 {sstats.p50_ms:.2f} ms  p99 {sstats.p99_ms:.2f} ms  "
              f"{sstats.per_sec:,.0f} q/s")


if __name__ == "__main__":
    main()
