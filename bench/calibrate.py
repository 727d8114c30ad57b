#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process.

  python3 bench/calibrate.py --workload <cell> --seeds 1-12 \
      --control-seeds 101-106 --fault-seeds 201-203

For each of ``--seeds``: the program's epochs that are compared, driven
as the benchmark drives them (``cell.first_epochs`` and a window through
the last epoch compared), against the plain reference; the largest of
each number over the seeds is its lower reading.  For each of
``--control-seeds``: the control, the reference computed in bfloat16 in
the program's place.  For each of ``--fault-seeds``: the reference with
each fault of ``FAULTS`` planted, in the program's place.  The benchmark's
own runs never run these.  One JSON line per reading, then a summary
line with each number's lower reading and the smallest reading of the
control and of each fault.  Refuses to run without a TPU.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


FAULTS = ("unchanged", "half", "no_pull")


def program_numbers(cell, gd, g, seed: int, used: list, counter) -> tuple:
    """(numbers, the parameter tree) of the program against the
    reference on ``seed``."""
    from bench import cell as cell_mod
    from bench import compare, harness

    interval = cell.traffic["sync_interval"]
    prog = cell_mod.build(cell, gd, seed, used)
    steps = cell_mod.first_epochs(prog, interval)
    cell_mod.window(prog, steps, 0.0, counter)
    got = cell_mod.outputs(steps, cell.config["adam_b1"])
    del prog, steps
    ref, params0 = harness.reference_outputs(cell, gd, g, seed,
                                             got["params3"])
    return (compare.numbers(got, ref, params0, harness.layout_ids(gd),
                            interval), got["params3"])


def stand_in_numbers(cell, gd, g, seed: int, like, kinds) -> dict:
    """For each of ``kinds``, the reference in the program's place, in
    bfloat16 (``"control"``) or with that fault planted, against the
    float32 reference, on ``seed``."""
    import jax.numpy as jnp

    from bench import compare, harness

    interval = cell.traffic["sync_interval"]
    ids = harness.layout_ids(gd)
    ref, params0 = harness.reference_outputs(cell, gd, g, seed, like)
    out = {}
    for kind in kinds:
        control = kind == "control"
        low, _ = harness.reference_outputs(
            cell, gd, g, seed, like, fault=None if control else kind,
            dtype=jnp.bfloat16 if control else jnp.float32)
        out[kind] = compare.numbers(
            compare.in_program_layout(low, ids, interval), ref, params0,
            ids, interval)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, required=True)
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    args = ap.parse_args()

    import jax

    from bench import cell as cell_mod
    from bench import compare, graphs, harness, spec
    from repro.launch.compile_cache import enable_compile_cache

    cell = spec.load_cell(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit(f"calibrate: {args.workload} needs {cell.chips} "
                         f"TPU chips; found {devices}")
    enable_compile_cache()
    counter = cell_mod.CompileCounter()
    gd = graphs.load(ROOT, cell.graph, cell.config["num_parts"])
    g = harness.reference_graph(cell, gd)
    readings = {}
    like = None

    def emit(kind, seed, nums, t):
        readings.setdefault(kind, []).append(nums)
        print(json.dumps({"kind": kind, "seed": seed, **nums,
                          "seconds": time.perf_counter() - t}), flush=True)

    for seed in args.seeds:
        t = time.perf_counter()
        nums, like = program_numbers(cell, gd, g, seed,
                                     devices[:cell.chips], counter)
        emit("program", seed, nums, t)
    for seed in sorted(set(args.control_seeds) | set(args.fault_seeds)):
        t = time.perf_counter()
        kinds = (["control"] if seed in args.control_seeds else []) + (
            list(FAULTS) if seed in args.fault_seeds else [])
        for kind, nums in stand_in_numbers(cell, gd, g, seed, like,
                                           kinds).items():
            emit(kind, seed, nums, t)
    summary = {k: {"lower": max(r[k] for r in readings["program"]),
                   **{kind: min(r[k] for r in rs)
                      for kind, rs in readings.items() if kind != "program"}}
               for k in compare.NUMBERS}
    print(json.dumps({"kind": "summary", "workload": args.workload,
                      **summary}), flush=True)


if __name__ == "__main__":
    main()
