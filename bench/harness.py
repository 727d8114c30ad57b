"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and (``--trace 1``) the per-layer metrics.

Every line it logs goes to standard error; the numbers compared, each
beside its limit, are the last lines there.  ``run`` returns the result
object that ``run.py`` prints as the last line of standard output.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import cell as cell_mod
from bench import compare, counts, graphs, inputs, reference, spec
from bench import trace_reduce as tr

GIB = 2 ** 30


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class TraceView:
    """The traced window, as the metric readers see it."""
    trace: tr.Trace
    op_names: dict          # HLO op name -> op_name metadata
    lo: int
    hi: int

    def op_name(self, hlo_op: str) -> str:
        return self.op_names.get(hlo_op, "")

    def op_s(self, pred) -> float:
        """Device seconds, summed over chips, of the ops for which
        ``pred(hlo op name, op_name)`` holds."""
        return tr.op_ns(self.trace, lambda n: pred(n, self.op_name(n)),
                        self.lo, self.hi) / 1e9

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips traced."""
        busy = tr.busy_ns(self.trace, self.lo, self.hi)
        return sum(busy.values()) / max(len(busy), 1) / 1e9

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader (``bench/metrics/<name>.py``)
    reads."""
    cell: spec.Cell
    chips: int
    epochs: int             # epochs in the window
    window_s: float         # host clock, first dispatch to last epoch end
    setup: dict             # graph_s, compile_s (host clock)
    counts: dict            # counts.epoch(...)
    peak: dict              # this chip's entry of peaks.json
    trace: TraceView | None


def peaks(root: Path, kind: str) -> dict:
    table = spec.read_json(spec.bench_dir(root) / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"({sorted(table)}); add its published peaks")
    return table[kind]


def assignment(data: dict, num_nodes: int) -> np.ndarray:
    """Node -> part, from the partition's local rows; every node must lie
    in exactly one part."""
    ids, valid = np.asarray(data["local_ids"]), np.asarray(
        data["local_valid"])
    assign = np.full(num_nodes, -1, np.int64)
    seen = np.zeros(num_nodes, np.int64)
    for m in range(ids.shape[0]):
        nodes = ids[m][valid[m]]
        assign[nodes] = m
        np.add.at(seen, nodes, 1)
    if not (seen == 1).all():
        raise ValueError("the partition does not hold every node exactly "
                         "once")
    return assign


def reference_model(cell: spec.Cell) -> reference.Model:
    c, g = cell.config, cell.graph
    return reference.Model(
        model=cell.model, heads=c["heads"], num_layers=c["num_layers"],
        hidden=c["hidden_dim"], num_classes=g["num_classes"],
        normalize=c["normalize"], lr=c["learning_rate"], b1=c["adam_b1"],
        b2=c["adam_b2"], eps=c["adam_eps"],
        sync_interval=cell.traffic["sync_interval"])


def reference_graph(cell: spec.Cell, gd: graphs.GraphData) -> dict:
    return reference.prepare(gd.edges, assignment(gd.data, len(
        gd.graph.labels)), gd.graph.labels, gd.graph.train_mask,
        cell.config["num_parts"])


def reference_outputs(cell: spec.Cell, gd: graphs.GraphData, g: dict,
                      seed: int, like, dtype=None,
                      fault: str | None = None) -> tuple[dict, dict]:
    """(the reference's epochs that are compared, the initial parameters)
    from the seed's features and weights, drawn anew."""
    import jax.numpy as jnp

    x_global, params0 = inputs.make(seed, gd.graph.labels, cell.graph, like)
    out = reference.run(reference_model(cell), g, x_global[:-1], params0,
                        compare.last_epoch(cell.traffic["sync_interval"]),
                        dtype=dtype or jnp.float32, fault=fault)
    return out, reference.host(params0)


def layout_ids(gd: graphs.GraphData) -> dict:
    """The program's slot-to-node and halo-row-to-node maps."""
    return {k: np.asarray(gd.data[k]) for k in ("store_ids", "halo_ids")}


def memory_line(compiled) -> str:
    """The compiled epoch's own account of its device memory."""
    ma = compiled.memory_analysis()
    if ma is None:
        return "memory analysis: none"
    return ("memory analysis of the epoch (B): " + ", ".join(
        f"{k} {getattr(ma, k + '_size_in_bytes', 0)}" for k in (
            "argument", "output", "alias", "temp", "generated_code")))


def _label(view: TraceView):
    def label(name: str) -> str:
        op = view.op_name(name).replace("jit(epoch_fn)/", "")
        return f"{name} {op[-100:]}".strip()
    return label


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, t0: float, require_chip: bool = True,
        cache_dir: Path = graphs.CACHE_DIR) -> dict:
    import jax

    root = Path(root)
    cell = spec.load_cell(root, workload)
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX's first device is "
                         f"{devices[0].platform!r}); refusing to run")
    if len(devices) < cell.chips:
        raise SystemExit(f"bench: {workload} needs {cell.chips} chips, "
                         f"found {len(devices)}")
    used = devices[:cell.chips]
    kind = used[0].device_kind
    t_chips = time.perf_counter()
    peak = peaks(root, kind) if require_chip else None

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    counter = cell_mod.CompileCounter()

    gd = graphs.load(root, cell.graph, cell.config["num_parts"], cache_dir)
    st = gd.data["struct"]
    fill = counts.ell_fill(gd.data)
    log(f"graph {cell.graph['name']}: {len(gd.graph.labels)} nodes, "
        f"{len(gd.edges)} edges, {cell.config['num_parts']} parts of "
        f"{gd.data['local_ids'].shape[1]} rows, halo "
        f"{gd.data['halo_ids'].shape[1]} rows; in-ELL width "
        f"{st['in_nbr'].shape[-1]}, out-ELL width "
        f"{st['out_nbr'].shape[-1]} (cache {'hit' if gd.hit else 'miss'},"
        f" {gd.seconds:.2f} s)")
    log(f"worklist occupancy {gd.data['_worklist'].occupancy:.4f}; ELL "
        f"slots filled (nnz / slots): in {fill['in']:.4f}, out "
        f"{fill['out']:.4f}")
    work = counts.epoch(cell.config, cell.graph["feature_dim"],
                        cell.graph["num_classes"], gd.data, root)
    log("required work per epoch: " + ", ".join(
        f"{k} {v:.6g}" for k, v in work.items()))

    prog = cell_mod.build(cell, gd, seed, used)
    log(memory_line(prog.compiled))
    for (layer, side, rung), n in sorted(
            cell_mod.kernel_calls(prog.hlo).items()):
        where = f"layer {layer} {side}" if layer >= 0 else side
        log(f"aggregation call {where}: {rung} kernel"
            + (f" x{n}" if n > 1 else ""))
    interval = cell.traffic["sync_interval"]
    t_warm = time.perf_counter()
    steps = cell_mod.first_epochs(prog, interval)
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.2f} s: to the chips {t_chips - t0:.2f}, graph "
        f"{gd.seconds:.2f}, state and inputs placed {prog.place_s:.2f}, "
        f"compile (or cache load) {prog.compile_s:.2f}, epochs 1-"
        f"{len(steps.losses)} {time.perf_counter() - t_warm:.2f}; peak "
        f"{cell_mod.peak_bytes(used)} B")

    trace_dir = spec.bench_dir(root) / ".traces" / f"{workload}-{seed}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    try:
        cell_mod.window(prog, steps, seconds, counter)
    finally:
        if trace:
            jax.profiler.stop_trace()
    log(f"window: epochs {len(steps.losses) - steps.epochs + 1}-"
        f"{len(steps.losses)} in {steps.window_s:.3f} s; compilations in "
        f"the window: {steps.compiles}")
    if steps.compiles:
        raise SystemExit("bench: the window compiled; every program it "
                         "runs must be compiled during set-up")
    mem = cell_mod.peak_bytes(used)
    losses = [float(x) for x in steps.losses]
    log("losses: " + " ".join(f"{x:.6f}" for x in losses))
    log(f"peak after the window {mem} B")
    got = cell_mod.outputs(steps, cell)
    like = got["params3"]
    hlo, compile_s = prog.hlo, prog.compile_s
    epochs, window_s = steps.epochs, steps.window_s
    del prog, steps
    gc.collect()

    t = time.perf_counter()
    ref, params0 = reference_outputs(cell, gd, reference_graph(cell, gd),
                                     seed, like)
    nums = compare.numbers(got, ref, params0, layout_ids(gd), interval)
    correct, rows = compare.verdict(nums, cell.limits)
    log(f"reference {time.perf_counter() - t:.2f} s; leaves left out of "
        f"change_gap: {nums['leaves_left_out']}")

    device = {"platform": used[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": len(losses),
              "failed": int(sum(not np.isfinite(x) for x in losses))}
    if trace:
        view = _trace_view(trace_dir, hlo)
        shutil.rmtree(trace_dir, ignore_errors=True)
        record = Run(cell=cell, chips=cell.chips, epochs=epochs,
                     window_s=window_s,
                     setup={"graph_s": gd.seconds, "compile_s": compile_s},
                     counts=work,
                     peak=peak, trace=view)
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(root, m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if view is not None:
            device.update(busy_s=view.busy_s(), window_s=view.window_s)
            result["breakdown"] = {
                "device_ops": [[k, v / 1e9] for k, v in tr.top_ops(
                    view.trace, _label(view), view.lo, view.hi)],
                "idle_gaps": [[k, v / 1e9] for k, v in tr.idle_gaps(
                    view.trace, view.lo, view.hi)[:10]]}
    else:
        e2e = {"epoch_s": window_s / epochs,
               "peak_hbm_gib": mem / GIB, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        log(f"check {k} {v!r} limit {lim!r}")
    log(f"correct {json.dumps(correct)}")
    return result


def _trace_view(trace_dir: Path, hlo: str) -> TraceView | None:
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    if not files:
        return None
    trace = tr.read_xplane(files[-1])
    if not trace.devices:
        return None
    lo, hi = tr.span(trace, "bench/window")
    return TraceView(trace=trace, op_names=tr.hlo_op_names(hlo), lo=lo,
                     hi=hi)
