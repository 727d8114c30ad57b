#!/usr/bin/env python3
"""The benchmark of DIGEST training on TPU.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: builds
the cell's epoch through the trainer's own calls, warms it up (set-up),
trains whole epochs for ``--seconds``, compares the first three epochs
with the plain reference, and prints one JSON object as the last line of
standard output.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.
It refuses to run (non-zero exit, no result) without a TPU, or with
fewer chips than the cell asks for.
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
# libtpu writes its logs under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from bench import harness

    result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), t0=T0)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
