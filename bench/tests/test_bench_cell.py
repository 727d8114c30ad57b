"""A whole run of each cell at a tiny size on the CPU (the look for a chip
skipped): it is correct, and each fault the cell can have, planted in
the timed path underneath, turns ``correct`` false; so does the control,
the reference computed in bfloat16 in the program's place."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import calibrate, compare, graphs, harness, spec  # noqa: E402
from bench import cell as cell_mod  # noqa: E402

TINY_NODES = 1600


def make_tiny_root(root: Path) -> Path:
    """The benchmark's data files with the graph cut to a few thousand
    nodes, plus two cells held to the committed cell's limits: one
    device at sync interval 1 (gather pull), and four devices at interval
    1 with the collective pull."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["workloads"] += [
        {"name": "gcn-products120k-n1", "config": "digest-gcn",
         "traffic": "n1", "chips": 1, "why": "test"},
        {"name": "gcn-products120k-n1-c4", "config": "digest-gcn",
         "traffic": "n1-collective", "chips": 4, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(
                        "__pycache__", ".graph_cache", ".traces", "tests"))
    limits = root / "bench" / "limits"
    for cell in ("gcn-products120k-n1", "gcn-products120k-n1-c4"):
        shutil.copy(limits / "gcn-products120k-n10.json",
                    limits / f"{cell}.json")
    for name, mode in (("n1", "gather"), ("n1-collective", "collective")):
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(
            {"sync_interval": 1, "precision": "fp32", "pull_mode": mode}))
    for folder, name in (("graphs", "products-120k"),
                         ("configs", "digest-gcn")):
        path = root / "bench" / folder / f"{name}.json"
        data = json.loads(path.read_text())
        data["num_nodes"] = TINY_NODES
        path.write_text(json.dumps(data))
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    was = jax.config.jax_compilation_cache_dir
    yield make_tiny_root(tmp_path_factory.mktemp("bench"))
    jax.config.update("jax_compilation_cache_dir", was)


def run_cell(root, workload, seed=5, trace=False):
    return harness.run(root, workload, seed, 0.1, trace,
                       t0=time.perf_counter(), require_chip=False,
                       cache_dir=root / "cache")


@pytest.mark.parametrize("workload", ["gcn-products120k-n10",
                                      "gcn-products120k-n1"])
def test_tiny_cell_is_correct(tiny, workload):
    res = run_cell(tiny, workload, seed=2 ** 31 + 11)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(compare.NUMBERS)
    assert set(res["metrics"]) == {"epoch_s", "peak_hbm_gib", "setup_s"}
    interval = spec.load_cell(tiny, workload).traffic["sync_interval"]
    assert res["attempted"] >= compare.last_epoch(interval)
    assert res["failed"] == 0


def test_traced_run_reports_per_layer_metrics(tiny):
    res = run_cell(tiny, "gcn-products120k-n10", trace=True)
    assert res["correct"] is True
    # No TPU plane on the CPU: only the host-clock metrics are read.
    assert set(res["metrics"]) == {"setup.graph_s", "setup.compile_s"}
    assert not (tiny / "bench" / ".traces" / "gcn-products120k-n10-5"
                ).exists()


def test_graph_cache_hits_and_rebuilds_on_a_changed_recipe(tiny, tmp_path):
    cell = spec.load_cell(tiny, "gcn-products120k-n10")
    first = graphs.load(tiny, cell.graph, 8, tmp_path)
    again = graphs.load(tiny, cell.graph, 8, tmp_path)
    assert not first.hit and again.hit
    for k in ("local_ids", "halo_slots", "pull_send"):
        np.testing.assert_array_equal(first.data[k], again.data[k])
    np.testing.assert_array_equal(first.edges, again.edges)
    assert again.data["_worklist"].occupancy == \
        first.data["_worklist"].occupancy
    changed = dict(cell.graph, structure_seed=1)
    assert not graphs.load(tiny, changed, 8, tmp_path).hit


def _unchanged_state(monkeypatch):
    from repro.launch import train_gnn
    real = train_gnn.make_epoch_fn

    def factory(*args, **kw):
        epoch = real(*args, **kw)
        return lambda state, data: (state, epoch(state, data)[1])
    monkeypatch.setattr(train_gnn, "make_epoch_fn", factory)


def _half_batch(monkeypatch):
    from repro.core import halo_exchange
    real = halo_exchange.per_subgraph

    def per_subgraph(fn, mesh, in_axes, **kw):
        run = real(fn, mesh, in_axes, **kw)

        def half(*args):
            (losses, aux), grads = run(*args)
            keep = losses.shape[0] // 2
            twice = lambda x: jnp.concatenate([x[:keep], x[:keep]])
            return (twice(losses), aux), jax.tree.map(twice, grads)
        return half
    monkeypatch.setattr(halo_exchange, "per_subgraph", per_subgraph)


def _altered_push(monkeypatch):
    from repro.core import halo_exchange
    real = halo_exchange.push

    def push(store, local_slots, local_valid, reps, *rest):
        return real(store, local_slots, local_valid,
                    reps.at[0].multiply(-1.0), *rest)
    monkeypatch.setattr(halo_exchange, "push", push)


def _pull_left_out(monkeypatch):
    from repro.core import halo_exchange
    real = halo_exchange.pull_slab
    monkeypatch.setattr(halo_exchange, "pull_slab", lambda *a: jax.tree.map(
        jnp.zeros_like, real(*a)))


def _altered_pulled_row(monkeypatch):
    from repro.core import halo_exchange
    real = halo_exchange.pull_slab

    def pull_slab(*args):
        slab = real(*args)
        return dict(slab, data=slab["data"].at[0, 0, 0].multiply(-1.0))
    monkeypatch.setattr(halo_exchange, "pull_slab", pull_slab)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_push, _pull_left_out,
                                   _altered_pulled_row])
def test_a_fault_in_the_timed_path_is_not_correct(tiny, monkeypatch,
                                                  fault):
    fault(monkeypatch)
    res = run_cell(tiny, "gcn-products120k-n10", seed=7)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("workload", ["gcn-products120k-n10",
                                      "gcn-products120k-n1"])
def test_the_control_fails_and_the_program_passes(tiny, workload):
    cell = spec.load_cell(tiny, workload)
    gd = graphs.load(tiny, cell.graph, cell.config["num_parts"],
                     tiny / "cache")
    g = harness.reference_graph(cell, gd)
    nums, like = calibrate.program_numbers(
        cell, gd, g, 21, jax.devices()[:1], cell_mod.CompileCounter())
    assert compare.verdict(nums, cell.limits)[0] is True
    control = calibrate.stand_in_numbers(cell, gd, g, 22, like,
                                         ["control"])["control"]
    assert compare.verdict(control, cell.limits)[0] is False, control


FOUR_DEVICES = textwrap.dedent("""
    import json, sys, time
    from pathlib import Path
    sys.path.insert(0, {repo!r})
    from bench import harness
    if sys.argv[1] == "no-exchange":
        import jax
        from repro.core import halo_exchange
        real = halo_exchange.collective_pull
        halo_exchange.collective_pull = lambda *a, **k: jax.tree.map(
            jax.numpy.zeros_like, real(*a, **k))
    root = Path(sys.argv[2])
    res = harness.run(root, "gcn-products120k-n1-c4", 9, 0.1, False,
                      t0=time.perf_counter(), require_chip=False,
                      cache_dir=root / "cache")
    print(json.dumps(res))
""")


@pytest.mark.parametrize("mode,correct", [("sound", True),
                                          ("no-exchange", False)])
def test_four_device_cell_and_exchange_left_out(tiny, mode, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES.format(repo=str(REPO)), mode,
         str(tiny)], env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is correct, out["checks"]
