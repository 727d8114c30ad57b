#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reads.

  python3 bench/testdata/record_trace.py OUT_DIR    # on 1 to 4 TPU chips

Three steps of a tiny program over the chips (up to four: a matmul, an
all-reduce and an all-to-all inside ``shard_map``), under the
benchmark's own host spans (``bench/window``, ``bench/dispatch``,
``bench/block``).  Writes ``chip_trace.xplane.pb`` and
``chip_trace.hlo.txt`` (the compiled program, for its op names) to
OUT_DIR.
"""
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    devices = jax.devices()[:4]
    if devices[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    n = len(devices)
    mesh = jax.make_mesh((n,), ("d",), devices=devices)

    def body(a):
        b = jnp.tanh(a @ a.T) @ a
        s = jax.lax.psum(b, "d")
        return jax.lax.all_to_all(s, "d", 0, 0, tiled=True) + 1.0

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("d"),
                                 out_specs=P("d")))
    x = jax.device_put(jnp.ones((n * 1024, 1024), jnp.float32),
                       NamedSharding(mesh, P("d")))
    compiled = step.lower(x).compile()
    compiled(x).block_until_ready()
    trace_dir = out / "trace"
    jax.profiler.start_trace(str(trace_dir))
    ann = jax.profiler.TraceAnnotation
    with ann("bench/window"):
        for _ in range(3):
            with ann("bench/dispatch"):
                y = compiled(x)
            with ann("bench/block"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    pb = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    shutil.copy(pb, out / "chip_trace.xplane.pb")
    shutil.rmtree(trace_dir)
    (out / "chip_trace.hlo.txt").write_text(compiled.as_text())


if __name__ == "__main__":
    main()
