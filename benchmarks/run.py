"""Benchmark harness: one module per paper table/figure.

Usage:
  PYTHONPATH=src python -m benchmarks.run             # all
  PYTHONPATH=src python -m benchmarks.run table1 fig6 # subset
  REPRO_BENCH_SCALE=0.5 ... (scale datasets/epochs)

Prints ``name,us_per_call,derived`` CSV rows.
"""
import sys
import time
import traceback

from benchmarks.common import emit
from repro.launch.compile_cache import enable_compile_cache

BENCHES = ["table1_f1_speedup", "fig3_curves", "fig4_time_per_epoch",
           "fig5_scalability", "fig6_sync_interval", "fig7_straggler",
           "fig9_memory_ratio", "thm1_error_bound", "comm_complexity",
           "kernel_bench", "serve_bench", "sampling_variance",
           "sat_prediction"]


def main() -> int:
    wanted = sys.argv[1:]
    enable_compile_cache()
    mods = [b for b in BENCHES
            if not wanted or any(w in b for w in wanted)]
    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            emit(mod.run())
            print(f"# {name} done in {time.perf_counter()-t0:.1f}s",
                  flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED", flush=True)
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
