#!/usr/bin/env python3
"""Smoke run of DIGEST training on a TPU, through the trainer's own path.

One chip (no arguments): the paper's configuration
(``repro.configs.digest_gcn``: GCN, 3 layers, hidden 128, 8 parts, sync
interval 10, Adam 5e-3) on products-sim at ``--scale`` 10 (~120k nodes),
built and run by the calls ``repro.launch.train_gnn`` makes —
``prepare_graph_data`` → ``init_state`` → the jitted epoch with
``subgraph_shardings`` (``train_gnn.jit_epoch``) → ``evaluate``.  It
trains ``--epochs`` epochs (PUSH at epoch 1, PULL at epoch 10) with the
halo store in fp32 and then in int8, the aggregation in the compiled
Pallas kernels (``backend="auto"``), evaluates on the full graph, and
checks that

  * every epoch's loss and the evaluation loss are finite;
  * every aggregation call of the compiled epoch, and each layer's of
    the compiled evaluation, is a Pallas kernel (``tpu_custom_call``);
    the layer, side and ladder rung of each are read from the compiled
    program's op metadata;
  * on real inputs (subgraph 0's features, and its halo slab pulled at
    epoch 10) the kernels agree with the jnp reference to ``KERNEL_TOL``
    of the reference's largest magnitude, on every rung: the rung the
    ladder selects, the resident kernel on a slice of the table, the
    chunk-skipping kernel with the real worklist, and the SAT predictor
    epilogue.

``--backend jnp`` runs the same phases with the jnp reference
aggregation, for comparison of the times; it skips the kernel checks.

Four chips (``--chips 4``): only the paths that cross chips, each against
the same path on a one-device mesh in the same process — the collective
epoch (``pull_mode="collective"``, data axis 4, 8 parts: two subgraphs
and owner shards per device), the full-graph evaluation of the
one-device run's parameters on each mesh, and the sharded serving query
(``serve_query_sharded``) over a store refreshed from those parameters.
Losses must agree to ``LOSS_RTOL``, F1 to ``F1_ATOL``, the pushed store
and the pulled slab to ``STORE_ATOL`` (the pushed rows are
L2-normalized, so |x| <= 1), and the served logits to ``KERNEL_TOL`` of
their largest magnitude, and to ``DENSE_TOL`` against the full-graph
forward.  The programs are partitioned differently and
reassociate their float32 sums, so bitwise equality is not expected.

It refuses to run unless JAX's first device is a TPU.  Every line but the
last is a log line; the last is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed.  The times it prints are smoke output, not a
benchmark.

  python3 chip_smoke.py                  # one chip
  python3 chip_smoke.py --backend jnp    # one chip, jnp aggregation
  python3 chip_smoke.py --chips 4        # the four-chip phase
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import math
import os
import re
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
# libtpu writes its logs under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

KERNEL_TOL = 1e-4     # kernel vs jnp reference, relative to max |reference|
LOSS_RTOL = 1e-4      # four-chip vs one-device losses, relative
F1_ATOL = 1e-3        # four-chip vs one-device evaluation micro-F1
STORE_ATOL = 1e-5     # four-chip vs one-device store and slab rows
# Served logits vs the full-graph forward, relative to max |logit|.  Both
# end in a dense product at the TPU's default matmul precision, which
# rounds float32 inputs to bfloat16 (2^-8 relative); the aggregations
# feeding it differ by float32 reassociation (split in-shard + halo sums
# against one fused sum), enough to move an input across a rounding
# boundary.  A wrong or missing row moves a logit by about max |logit|.
DENSE_TOL = 2.0 ** -8
RESIDENT_ROWS = 2048  # table slice small enough for the resident kernel

# The jitted kernel entry point each ladder rung compiles under; the name
# survives into the compiled custom call's op_name metadata.
RUNG_OF_KERNEL = {"jit(halo_spmm_pallas)": "resident",
                  "jit(halo_spmm_stream_pallas)": "stream",
                  "jit(halo_spmm_skip_pallas)": "skip"}


def log(msg: str) -> None:
    print(msg, flush=True)


def check(failures: list, ok: bool, msg: str) -> None:
    """Log one check; a failed one is added to ``failures``."""
    log(("ok   " if ok else "FAIL ") + msg)
    if not ok:
        failures.append(msg)


def require_tpu(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{devices[0].platform!r}); refusing to run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"found {len(devices)}")
    return devices


def build_data(scale: float):
    from repro.configs.digest_gcn import CONFIG
    from repro.core import prepare_graph_data
    from repro.graph import make_dataset

    t = time.perf_counter()
    g = make_dataset(CONFIG.dataset, scale=scale)
    data = prepare_graph_data(g, CONFIG.num_parts)
    m, s = data["local_ids"].shape
    h = data["halo_ids"].shape[1]
    log(f"graph: {CONFIG.dataset} scale={scale}: {g.num_nodes} nodes, "
        f"{len(g.indices) // 2} edges; {m} parts of {s} rows, halo {h} "
        f"rows per part; in-ELL width {data['struct']['in_nbr'].shape[-1]},"
        f" out-ELL width {data['struct']['out_nbr'].shape[-1]}, worklist "
        f"occupancy {data['_worklist'].occupancy:.3f} "
        f"(set-up {time.perf_counter() - t:.1f}s)")
    return g, data


def evaluation(cfg, params, tdata, mesh) -> dict:
    """``evaluate`` on the full graph over ``mesh``, compiled ahead of
    the call; compile and run timed apart."""
    import jax

    from repro.core import evaluate

    t = time.perf_counter()
    ev_fn = evaluate.lower(cfg, params, tdata, mesh).compile()
    compile_s = time.perf_counter() - t
    t = time.perf_counter()
    ev = jax.device_get(ev_fn(params, tdata))
    return dict(eval={k: float(v) for k, v in ev.items()},
                eval_hlo=ev_fn.as_text(), eval_compile_s=compile_s,
                eval_s=time.perf_counter() - t)


def train(g, data, precision: str, mesh, epochs: int, *,
          pull_mode: str = "gather", backend: str = "auto",
          evaluate: bool = True):
    """Build the epoch as ``train_gnn.main`` does, compile it ahead of
    the first call (timed), run ``epochs`` epochs, then (``evaluate``)
    evaluate the trained parameters on the full graph."""
    import jax

    from repro.configs.digest_gcn import CONFIG as C
    from repro.core import HaloPrecision, TrainSettings, init_state
    from repro.launch.train_gnn import jit_epoch, model_config
    from repro.optim import adam

    cfg = model_config(g, data, model=C.model, hidden_dim=C.hidden_dim,
                       num_layers=C.num_layers, backend=backend)
    opt = adam(C.learning_rate)
    settings = TrainSettings(sync_interval=C.sync_interval, mode="digest",
                             pull_mode=pull_mode,
                             precision=HaloPrecision(precision))
    state = init_state(cfg, opt, data, precision=settings.precision)
    epoch_fn, state, tdata = jit_epoch(cfg, opt, settings, data, state,
                                       mesh)
    t = time.perf_counter()
    compiled = epoch_fn.lower(state, tdata).compile()
    compile_s = time.perf_counter() - t
    losses, times, pushed = [], [], None
    for e in range(epochs):
        t = time.perf_counter()
        state, metrics = epoch_fn(state, tdata)
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t)
        losses.append(float(metrics["loss"]))
        if e == 0:
            pushed = jax.device_get(state["store"])
    run = dict(cfg=cfg, state=state, tdata=tdata, hlo=compiled.as_text(),
               compile_s=compile_s, losses=losses, times=times,
               pushed=pushed, slab=jax.device_get(state["cache"]))
    if evaluate:
        run.update(evaluation(cfg, state["params"], tdata, mesh))
    return run


def kernel_calls(hlo: str) -> collections.Counter:
    """Count the Pallas kernels of a compiled program by (layer, side,
    rung), read from each custom call's op metadata: the layer's named
    scope (-1 outside one), ``jit(spmm)`` for the in-subgraph side, and
    the jitted kernel entry point for the ladder rung."""
    calls = collections.Counter()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        found = re.search(r'op_name="([^"]*)"', line)
        op = found.group(1) if found else ""
        layer = re.search(r"layer_(\d+)", op)
        calls[(int(layer.group(1)) if layer else -1,
               "local" if "jit(spmm)" in op else "halo",
               next((rung for name, rung in RUNG_OF_KERNEL.items()
                     if name in op), "other"))] += 1
    return calls


def log_kernel_calls(label: str, calls: collections.Counter) -> None:
    for (layer, side, rung), n in sorted(calls.items()):
        where = f"layer {layer} {side}" if layer >= 0 else side
        log(f"{label} {where}: {rung} kernel" + (f" x{n}" if n > 1 else ""))


def reference_cases(run: dict) -> dict:
    """Named aggregation calls on subgraph 0's real inputs: (kernel
    backend, array arguments, static arguments) of ``halo_spmm``."""
    import jax.numpy as jnp

    from repro.core.halo_exchange import layer_table

    cfg, tdata, st = run["cfg"], run["tdata"], run["tdata"]["struct"]
    x = tdata["x_global"][tdata["local_ids"][0]]
    zero = jnp.zeros((1, x.shape[1]), x.dtype)
    cache = {k: v[0] for k, v in run["state"]["cache"].items()}
    slab, scale = layer_table(cache, 0)     # layer 1's halo input rows
    pslab, pscale = layer_table(cache, 1)   # layer 2's, as SAT history
    local = dict(nbr=st["in_nbr"][0], wts=st["in_wts"][0],
                 data=jnp.concatenate([x, zero]))
    r = RESIDENT_ROWS - 1
    head = dict(nbr=jnp.where(local["nbr"] < r, local["nbr"], r),
                wts=local["wts"], data=jnp.concatenate([x[:r], zero]))
    halo = dict(nbr=st["out_nbr"][0], wts=st["out_wts"][0], data=slab,
                scale=scale, wl_ids=st["wl_ids"][0], wl_cnt=st["wl_cnt"][0])
    ladder = cfg.backend
    return {
        "layer 0 local": (ladder, local, {}),
        f"layer 0 local, first {r} table rows": (ladder, head, {}),
        "layer 1 halo": (ladder, halo,
                         dict(occupancy=cfg.halo_occupancy)),
        "layer 1 halo, chunk-skipping": ("pallas_skip", halo, {}),
        "layer 1 halo + SAT epilogue (gamma 0.5)": (
            ladder, dict(halo, pdata=pslab, pscale=pscale),
            dict(occupancy=cfg.halo_occupancy, gamma=0.5)),
    }


def kernel_vs_reference(run: dict) -> dict:
    """Per named case: (rungs of its compiled program, max |kernel − jnp|
    / max |jnp|)."""
    import jax
    import numpy as np

    from repro.kernels.spmm import halo_spmm

    out = {}
    for name, (backend, arrays, static) in reference_cases(run).items():
        arrays = {k: v for k, v in arrays.items() if v is not None}
        fn = jax.jit(functools.partial(halo_spmm, backend=backend, **static))
        compiled = fn.lower(**arrays).compile()
        rungs = sorted({rung for _, _, rung in
                        kernel_calls(compiled.as_text())})
        got = np.asarray(compiled(**arrays))
        want = np.asarray(halo_spmm(**arrays, backend="jnp", **static))
        out[name] = (rungs, float(np.abs(got - want).max())
                     / max(float(np.abs(want).max()), 1e-30))
    return out


def one_chip_phase(args) -> list:
    import jax

    from repro.kernels.spmm import RESIDENT_STRIPE_MAX_BYTES, resolve_backend
    from repro.launch.mesh import make_host_mesh

    g, data = build_data(args.scale)
    m, s = data["local_ids"].shape
    h = data["halo_ids"].shape[1]
    hidden = 128
    log(f"bytes per part: local table {(s + 1) * hidden * 4} (fp32), halo "
        f"slab {(h + 1) * hidden * 4} (fp32) / {(h + 1) * (hidden + 4)} "
        f"(int8 + scale); resident budget {RESIDENT_STRIPE_MAX_BYTES}")
    kernels = resolve_backend(args.backend) != "jnp"
    log(f"aggregation backend: {args.backend} -> "
        f"{resolve_backend(args.backend)}"
        + ("" if kernels else "; kernel checks skipped"))
    mesh = make_host_mesh(devices=jax.devices()[:1])
    failures = []
    for precision in ("fp32", "int8"):
        try:
            run = train(g, data, precision, mesh, args.epochs,
                        backend=args.backend)
            log(f"[{precision}] compile {run['compile_s']:.2f}s")
            for e, (loss, sec) in enumerate(zip(run["losses"],
                                                run["times"]), 1):
                log(f"[{precision}] epoch {e}: loss {loss!r} "
                    f"({sec:.3f}s)")
            log(f"[{precision}] evaluate: compile "
                f"{run['eval_compile_s']:.2f}s, run {run['eval_s']:.3f}s; "
                + ", ".join(f"{k} {v!r}" for k, v in
                            sorted(run["eval"].items())))
            check(failures, all(math.isfinite(x) for x in run["losses"]),
                  f"[{precision}] every loss is finite")
            check(failures, all(math.isfinite(v)
                                for v in run["eval"].values()),
                  f"[{precision}] evaluation is finite")
            if kernels:
                layers = range(run["cfg"].num_layers)
                calls = kernel_calls(run["hlo"])
                log_kernel_calls(f"[{precision}] epoch", calls)
                sides = {(layer, side) for layer, side, _ in calls}
                check(failures, sides >= {(layer, side) for layer in layers
                                          for side in ("local", "halo")},
                      f"[{precision}] every aggregation call of the "
                      f"compiled epoch is a Pallas kernel")
                calls = kernel_calls(run["eval_hlo"])
                log_kernel_calls(f"[{precision}] evaluate", calls)
                sides = {(layer, side) for layer, side, _ in calls}
                check(failures, sides >= {(layer, "local")
                                          for layer in layers},
                      f"[{precision}] every layer of the compiled "
                      f"evaluation aggregates in a Pallas kernel")
                for name, (rungs, err) in kernel_vs_reference(run).items():
                    check(failures, err <= KERNEL_TOL and rungs != [],
                          f"[{precision}] {name} ({'/'.join(rungs)} "
                          f"kernel): max error {err:.3e} of max |ref| "
                          f"(tolerance {KERNEL_TOL})")
        except Exception:  # noqa: BLE001 — report, then run the next phase
            traceback.print_exc()
            failures.append(f"[{precision}] raised")
        stats = jax.devices()[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            log(f"[{precision}] peak device memory "
                f"{stats['peak_bytes_in_use']} bytes")
    return failures


def sharded_query(cfg, plan, data, params, store, mesh, q_rows):
    """``serve_query_sharded`` over ``mesh``: (logits, compile s, run s,
    kernel calls)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import serving

    scfg = serving.ServeConfig(batch_size=q_rows.shape[1])
    sdata = plan.sharded_data(data)
    store_sh, sdata_sh, q_sh = serving.serve_shardings(store, sdata, mesh)
    args = (jax.device_put(params, NamedSharding(mesh, P())),
            jax.device_put(store, store_sh),
            jax.device_put(sdata, sdata_sh), jax.device_put(q_rows, q_sh))
    t = time.perf_counter()
    compiled = serving.serve_query_sharded.lower(
        cfg, scfg, mesh, plan.halo_size, *args).compile()
    compile_s = time.perf_counter() - t
    t = time.perf_counter()
    out = jax.device_get(compiled(*args))
    return out, compile_s, time.perf_counter() - t, kernel_calls(
        compiled.as_text())


def four_chip_phase(args) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import (check_collective_geometry, full_graph_forward,
                            serving)
    from repro.launch.mesh import make_host_mesh

    g, data = build_data(args.scale)
    devices = jax.devices()
    meshes = {"1 device": make_host_mesh(devices=devices[:1]),
              "4 devices": make_host_mesh(data=4, devices=devices[:4])}
    runs = {}
    for name, mesh in meshes.items():
        k = check_collective_geometry(data, mesh)
        run = train(g, data, "fp32", mesh, args.epochs,
                    pull_mode="collective", evaluate=False)
        log(f"[{name}] collective epoch, {k} subgraph(s) per device; "
            f"compile {run['compile_s']:.2f}s; epochs "
            + ", ".join(f"{x!r} ({t:.3f}s)"
                        for x, t in zip(run["losses"], run["times"])))
        runs[name] = run
    ref, got = runs["1 device"], runs["4 devices"]
    # Both meshes evaluate the one-device run's parameters: the trained
    # parameters differ by the epochs' reassociation, enough to flip a
    # few predictions, and this compares the evaluation path alone.
    cfg = ref["cfg"]
    params = jax.device_get(ref["state"]["params"])
    for name, mesh in meshes.items():
        runs[name].update(evaluation(
            cfg, jax.device_put(params, NamedSharding(mesh, P())),
            runs[name]["tdata"], mesh))
        log(f"[{name}] evaluate: compile {runs[name]['eval_compile_s']:.2f}"
            f"s, run {runs[name]['eval_s']:.3f}s; "
            + ", ".join(f"{k} {v!r}"
                        for k, v in sorted(runs[name]["eval"].items())))
    failures = []
    loss_err = max(abs(a - b) / abs(b) for a, b in
                   zip(got["losses"], ref["losses"]))
    check(failures, loss_err <= LOSS_RTOL,
          f"losses agree: max relative difference {loss_err:.3e} "
          f"(tolerance {LOSS_RTOL})")
    for what, label in (("pushed", "store pushed at epoch 1"),
                        ("slab", f"halo slab after {args.epochs} epochs")):
        err = max(float(np.abs(np.asarray(got[what][k], np.float32)
                               - np.asarray(ref[what][k], np.float32)).max())
                  for k in ref[what])
        check(failures, err <= STORE_ATOL,
              f"{label} agrees: max difference {err:.3e} (tolerance "
              f"{STORE_ATOL})")
    for key in sorted(ref["eval"]):
        a, b = got["eval"][key], ref["eval"][key]
        if key.endswith("_loss"):
            err, tol = abs(a - b) / abs(b), LOSS_RTOL
        else:
            err, tol = abs(a - b), F1_ATOL
        check(failures, err <= tol,
              f"evaluate {key} agrees: difference {err:.3e} (tolerance "
              f"{tol})")

    # Serving: a store refreshed from the one-device run's parameters
    # (the full-graph forward's h^(L-1), as ``top_layer_reps``), queried
    # for the first valid local rows of every part.
    exact, reps = full_graph_forward(cfg, params, data)
    plan = serving.build_serve_plan(data)
    store = serving.make_refresh_fn(donate=False)(
        serving.init_serve_store(plan, cfg.hidden_dim), reps[-1],
        plan.refresh_data())
    batch = serving.ServeConfig().batch_size
    q_rows = np.full((plan.num_parts, batch), plan.part_rows, np.int32)
    gids = []
    for m in range(plan.num_parts):
        v = np.where(plan.local_valid[m])[0][:batch]
        q_rows[m, :len(v)] = v
        gids.append(plan.local_ids[m][v])
    q_rows = jnp.asarray(q_rows)
    served = {}
    for name, mesh in meshes.items():
        out, compile_s, run_s, calls = sharded_query(
            cfg, plan, data, params, store, mesh, q_rows)
        log(f"[{name}] serve_query_sharded {plan.num_parts}x{batch} rows: "
            f"compile {compile_s:.2f}s, run {run_s:.3f}s")
        log_kernel_calls(f"[{name}] serve_query_sharded", calls)
        check(failures, sum(calls.values()) >= 2,
              f"[{name}] the sharded query aggregates in Pallas kernels")
        served[name] = np.concatenate([out[m, :len(ids)]
                                       for m, ids in enumerate(gids)])
    exact = np.asarray(exact)[np.concatenate(gids)]
    scale = max(float(np.abs(exact).max()), 1e-30)
    for label, a, b, tol in (
            ("4 devices vs 1 device", served["4 devices"],
             served["1 device"], KERNEL_TOL),
            ("4 devices vs the full-graph forward", served["4 devices"],
             exact, DENSE_TOL)):
        err = float(np.abs(a - b).max()) / scale
        check(failures, err <= tol,
              f"served logits, {label}: max difference {err:.3e} of max "
              f"|logit| (tolerance {tol:.3e})")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip training phases; 4: only the "
                         "paths that cross chips (collective epoch, "
                         "evaluation, sharded serving query)")
    ap.add_argument("--scale", type=float, default=10.0,
                    help="products-sim scale (10: ~120k nodes)")
    ap.add_argument("--epochs", type=int, default=10,
                    help="epochs per run (the 10th pulls the store)")
    ap.add_argument("--backend", default="auto", choices=("auto", "jnp"),
                    help="one-chip aggregation: 'auto' (the compiled "
                         "Pallas kernels) or 'jnp' (the reference, for "
                         "comparison; skips the kernel checks)")
    args = ap.parse_args()
    if args.chips != 1 and args.backend != "auto":
        ap.error("--backend applies to the one-chip phase only")
    devices = require_tpu(args.chips)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{cache} ({entries} entries)")
    t = time.perf_counter()
    phase = one_chip_phase if args.chips == 1 else four_chip_phase
    failures = phase(args)
    log(f"total {time.perf_counter() - t:.1f}s")
    if failures:
        log(f"chip_smoke: {len(failures)} check(s) failed")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
