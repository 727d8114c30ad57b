"""Drive the program under test: build a cell's epoch through the
trainer's own calls, warm it up and run the measured window.

The run is built as ``repro.launch.train_gnn.main`` builds it:
``model_config`` -> ``init_state`` -> ``jit_epoch`` over a mesh of the
cell's chips; the benchmark only puts its own seeded features and
weights into the data and the state before ``jit_epoch`` places them.
The epoch is compiled ahead of the warm-up (timed as set-up) and the
window calls that compiled program, as every epoch of training does.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import time

import jax
import numpy as np

from bench import compare, inputs

# The jitted kernel entry point of each ladder rung; the name survives
# into the compiled custom call's op_name metadata.
RUNG_OF_KERNEL = {"jit(halo_spmm_pallas)": "resident",
                  "jit(halo_spmm_stream_pallas)": "stream",
                  "jit(halo_spmm_skip_pallas)": "skip"}


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


class CompileCounter:
    """Counts JAX's trace, lower and compile events in this process."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.count += 1


@dataclasses.dataclass
class Program:
    compiled: object
    state: dict
    tdata: dict
    hlo: str
    compile_s: float
    place_s: float        # state, seeded inputs and their transfer


def build(cell, gd, seed: int, devices: list) -> Program:
    from repro.core import (HaloPrecision, TrainSettings,
                            check_collective_geometry, init_state)
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train_gnn import jit_epoch, model_config
    from repro.optim import adam

    c, t = cell.config, cell.traffic
    cfg = model_config(gd.graph, gd.data, model=c["model"],
                       hidden_dim=c["hidden_dim"],
                       num_layers=c["num_layers"],
                       normalize=c["normalize"])
    opt = adam(c["learning_rate"], b1=c["adam_b1"], b2=c["adam_b2"],
               eps=c["adam_eps"])
    settings = TrainSettings(sync_interval=t["sync_interval"],
                             mode="digest", pull_mode=t["pull_mode"],
                             precision=HaloPrecision(t["precision"]))
    mesh = make_host_mesh(data=len(devices), devices=devices)
    if settings.pull_mode == "collective":
        check_collective_geometry(gd.data, mesh)
    t0 = time.perf_counter()
    data = dict(gd.data)
    state = init_state(cfg, opt, data, precision=settings.precision)
    data["x_global"], state["params"] = inputs.make(
        seed, gd.graph.labels, cell.graph, state["params"])
    state["opt_state"] = opt.init(state["params"])
    epoch_fn, state, tdata = jit_epoch(cfg, opt, settings, data, state,
                                       mesh)
    jax.block_until_ready((state, tdata))
    t1 = time.perf_counter()
    compiled = epoch_fn.lower(state, tdata).compile()
    t2 = time.perf_counter()
    return Program(compiled=compiled, state=state, tdata=tdata,
                   hlo=compiled.as_text(), compile_s=t2 - t1,
                   place_s=t1 - t0)


@dataclasses.dataclass
class Steps:
    """The epochs run so far: every loss (device scalars, epoch 1 first),
    what the comparison reads of the state after the epochs it names
    (host copies), and how the window went."""
    sync_interval: int
    losses: list = dataclasses.field(default_factory=list)
    compared: dict = dataclasses.field(default_factory=dict)
    pending: dict = dataclasses.field(default_factory=dict)
    epochs: int = 0       # epochs in the window
    window_s: float = 0.0
    compiles: int = 0     # compile events inside the window


def _compared(state: dict, epoch: int, sync_interval: int) -> dict:
    """What ``compare.numbers`` reads of the state after ``epoch``:
    Adam's first moment and the store after epoch 1, the parameters after
    epoch 3, and the halo tables pulled at epoch p."""
    p = compare.pull_epoch(sync_interval)
    out = {}
    if epoch == 1:
        out["m1"] = state["opt_state"]["m"]
        out["store"] = state["store"]["data"]
    if epoch == 3:
        out["params3"] = state["params"]
    if epoch == p:
        out["slab"] = state["cache"]["data"]
    return out


def _dispatch(prog: Program, steps: Steps) -> None:
    """Queue one epoch, and the copies to the host of what the comparison
    reads of its state (they start once the epoch has ended)."""
    prog.state, m = prog.compiled(prog.state, prog.tdata)
    steps.losses.append(m["loss"])
    epoch = len(steps.losses)
    picked = _compared(prog.state, epoch, steps.sync_interval)
    if picked:
        for a in jax.tree.leaves(picked):
            a.copy_to_host_async()
        steps.pending[epoch] = picked


def _collect(steps: Steps, upto: int) -> None:
    """Take the host copies of the epochs up to ``upto``, which have
    ended, and let their device arrays go."""
    for epoch in [e for e in steps.pending if e <= upto]:
        host = jax.tree.map(np.asarray, steps.pending.pop(epoch))
        steps.compared.update({(k, epoch): v for k, v in host.items()})


def first_epochs(prog: Program, sync_interval: int) -> Steps:
    """Set-up: epoch 1, which loads every program the window runs, and
    the epochs before the first pull of pushed rows (epoch p), so that
    the window opens with it: the epochs the comparison follows are the
    program's own first ones, through its one compiled call."""
    steps = Steps(sync_interval=sync_interval)
    for epoch in range(1, max(compare.pull_epoch(sync_interval), 2)):
        _dispatch(prog, steps)
        jax.block_until_ready(prog.state)
        _collect(steps, epoch)
    return steps


def window(prog: Program, steps: Steps, seconds: float,
           counter: CompileCounter) -> None:
    """Run whole epochs until ``seconds`` have passed, and at least
    through the last epoch compared, keeping one epoch queued behind the
    one the device runs.  The window is all the time from the first
    dispatch to the end of the last epoch."""
    ann = jax.profiler.TraceAnnotation
    before = counter.count
    need = compare.last_epoch(steps.sync_interval) - len(steps.losses)
    t0 = time.perf_counter()
    with ann("bench/window"):
        n = 0
        while True:
            with ann("bench/dispatch"):
                _dispatch(prog, steps)
            n += 1
            if n >= 2:
                with ann("bench/block"):
                    steps.losses[-2].block_until_ready()
                _collect(steps, len(steps.losses) - 1)
                # Epoch n, queued now, ends about one mean epoch later:
                # the window closes at the first epoch end past seconds.
                if (n >= need and (time.perf_counter() - t0) * n / (n - 1)
                        >= seconds):
                    break
        with ann("bench/block"):
            jax.block_until_ready(prog.state)
    steps.window_s = time.perf_counter() - t0
    steps.epochs = n
    steps.compiles = counter.count - before
    _collect(steps, len(steps.losses))


def outputs(steps: Steps, b1: float) -> dict:
    """What the program produced in the epochs compared, on the host, in
    the form ``compare.numbers`` takes."""
    p = compare.pull_epoch(steps.sync_interval)
    f32 = lambda tree: jax.tree.map(lambda a: np.asarray(a, np.float32),
                                    tree)
    got = steps.compared
    return {"losses": [float(x) for x in steps.losses[
                :compare.last_epoch(steps.sync_interval)]],
            "grad1": jax.tree.map(lambda m: m / (1.0 - b1),
                                  f32(got["m1", 1])),
            "params3": f32(got["params3", 3]),
            "stores": {1: list(got["store", 1])},
            "slab": got["slab", p]}


def peak_bytes(devices: list) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
