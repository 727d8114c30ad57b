#!/usr/bin/env python3
"""Record the chip trace of a tiny DIGEST epoch that
``test_bench_scopes.py`` reads.

  python3 bench/testdata/record_epoch_trace.py OUT_DIR    # on one TPU chip

Builds the cell ``gcn-products120k-n10``'s configuration on its graph
recipe cut to 1,600 nodes, at sync interval 1 (every epoch pulls and
pushes), through the benchmark's own calls (``bench.cell``): set-up runs
epoch 1, and a window of two epochs is traced under the benchmark's host
spans.  Writes ``epoch_trace.xplane.pb`` and ``epoch_trace.hlo.txt`` to
OUT_DIR: the compiled epoch's text, with each instruction's metadata cut
to its ``op_name``, and the serialized backend configs (the kernels'
bodies) and the source-location tables left out, to keep it small.
"""
import os
import re
import shutil
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

NODES = 1600
CELL = "gcn-products120k-n10"
TRAFFIC = {"sync_interval": 1, "precision": "fp32", "pull_mode": "gather"}
_CONFIG = re.compile(r",?\s*backend_config=")
_META = re.compile(r"metadata=\{[^}]*\}")
_OP_NAME = re.compile(r'op_name="[^"]*"')
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _skip_value(text: str, i: int) -> int:
    """The end of the quoted string or balanced braces at ``text[i]``."""
    if text[i] == '"':
        i += 1
        while text[i] != '"':
            i += 2 if text[i] == "\\" else 1
        return i + 1
    depth = 0
    while True:
        depth += {"{": 1, "}": -1}.get(text[i], 0)
        i += 1
        if depth == 0:
            return i


def slim(hlo: str) -> str:
    """The program text without backend configs and source-location
    tables, and with each instruction's metadata cut to its ``op_name``."""
    lines, table = [], False
    for line in hlo.splitlines():
        table = line in _TABLES or (table and line != "")
        if not table:
            lines.append(line)
    hlo = "\n".join(lines) + "\n"
    out, i = [], 0
    for m in _CONFIG.finditer(hlo):
        if m.start() < i:
            continue
        out.append(hlo[i:m.start()])
        i = _skip_value(hlo, m.end())
    out.append(hlo[i:])

    def keep_op_name(m):
        found = _OP_NAME.search(m.group(0))
        return "metadata={%s}" % (found.group(0) if found else "")
    return _META.sub(keep_op_name, "".join(out))


def main() -> None:
    import jax

    from bench import cell as cell_mod
    from bench import graphs, harness, spec

    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    devices = jax.devices()[:1]
    if devices[0].platform != "tpu":
        raise SystemExit("record_epoch_trace: needs a TPU")
    cell = spec.load_cell(ROOT, CELL)
    cell = spec.Cell(**{**cell.__dict__,
                        "config": dict(cell.config, num_nodes=NODES),
                        "graph": dict(cell.graph, num_nodes=NODES),
                        "traffic": dict(TRAFFIC)})
    with tempfile.TemporaryDirectory() as cache:
        gd = graphs.load(ROOT, cell.graph, cell.config["num_parts"],
                         Path(cache))
    prog = cell_mod.build(cell, gd, 1, devices)
    steps = cell_mod.first_epochs(prog, TRAFFIC["sync_interval"])
    trace_dir = out / "trace"
    # User annotations only on the host, no Python calls: a small file.
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level, options.python_tracer_level = 1, 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        cell_mod.window(prog, steps, 0.0, cell_mod.CompileCounter())
    finally:
        jax.profiler.stop_trace()
    harness.log(f"window: epochs {len(steps.losses) - steps.epochs + 1}-"
                f"{len(steps.losses)}, {steps.window_s:.4f} s")
    pb = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    shutil.copy(pb, out / "epoch_trace.xplane.pb")
    shutil.rmtree(trace_dir)
    (out / "epoch_trace.hlo.txt").write_text(slim(prog.hlo))
    for name in ("epoch_trace.xplane.pb", "epoch_trace.hlo.txt"):
        harness.log(f"{name}: {(out / name).stat().st_size} B")


if __name__ == "__main__":
    main()
