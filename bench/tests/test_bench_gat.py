"""The GAT cell, ``gat-flickr-n10``, at a tiny size on the CPU: a whole
run is correct against ``bench/models/gat.py``, the bfloat16 control and
each planted fault fail it, and the model file's ``pull`` is the
program's projection of the store."""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import calibrate, compare, graphs, harness, spec  # noqa: E402
from bench import cell as cell_mod  # noqa: E402
from test_bench_cell import TINY_NODES, make_tiny_root, run_cell  # noqa: E402

CELL = "gat-flickr-n10"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The tiny benchmark root with the GAT's graph and configuration cut
    to ``TINY_NODES`` as well."""
    was = jax.config.jax_compilation_cache_dir
    root = make_tiny_root(tmp_path_factory.mktemp("gat"))
    for folder, name in (("graphs", "flickr"), ("configs", "digest-gat")):
        path = root / "bench" / folder / f"{name}.json"
        data = json.loads(path.read_text())
        data["num_nodes"] = TINY_NODES
        path.write_text(json.dumps(data))
    yield root
    jax.config.update("jax_compilation_cache_dir", was)


def _loaded(tiny):
    cell = spec.load_cell(tiny, CELL)
    gd = graphs.load(tiny, cell.graph, cell.config["num_parts"],
                     tiny / "cache")
    return cell, gd


def test_tiny_gat_cell_is_correct(tiny):
    """The whole run, checked on every number the cell's limits name."""
    res = run_cell(tiny, CELL, seed=2 ** 31 + 11)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    limits = spec.load_cell(tiny, CELL).limits
    assert set(res["checks"]) == {k for k in compare.NUMBERS if k in limits}
    assert set(res["metrics"]) == {"epoch_s", "peak_hbm_gib", "setup_s"}
    interval = spec.load_cell(tiny, CELL).traffic["sync_interval"]
    assert res["attempted"] >= compare.last_epoch(interval)
    assert res["failed"] == 0


def test_traced_gat_run_reports_the_shared_per_layer_metrics(tiny):
    """A traced run of the GAT cell reads the per-layer metrics it shares
    with the GCN cell; on the CPU, with no TPU plane, the host-clock
    ones."""
    res = run_cell(tiny, CELL, seed=7, trace=True)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"setup.graph_s", "setup.compile_s"}
    shared = {m["name"] for m in spec.load_cell(tiny, CELL).per_layer
              if "gcn-products120k-n10" in m["workloads"]}
    assert {"epoch_mfu", "device_idle", "unscoped_ms", "pull_ms",
            "push_ms", "dense_ms", "opt_ms"} <= shared


def test_the_control_fails_and_the_gat_program_passes(tiny):
    cell, gd = _loaded(tiny)
    g = harness.reference_graph(cell, gd)
    nums, like = calibrate.program_numbers(
        cell, gd, g, 21, jax.devices()[:1], cell_mod.CompileCounter())
    assert compare.verdict(nums, cell.limits)[0] is True
    control = calibrate.stand_in_numbers(cell, gd, g, 22, like,
                                         ["control"])["control"]
    assert compare.verdict(control, cell.limits)[0] is False, control


@pytest.mark.parametrize("fault", calibrate.FAULTS)
def test_a_planted_fault_fails_the_gat_cell(tiny, fault):
    """Each fault the calibration plants, in the reference standing in for
    the GAT program, fails at least one check."""
    cell, gd = _loaded(tiny)
    g = harness.reference_graph(cell, gd)
    nums = calibrate.stand_in_numbers(cell, gd, g, 23,
                                      _gat_params(cell, gd), [fault])[fault]
    assert compare.verdict(nums, cell.limits)[0] is False, nums


def test_gat_pull_is_the_programs_projection_of_the_store(tiny):
    """The GAT model file's ``pull`` projects the store as the program's
    pull does (``core/digest.project_store_tables``): each hidden layer's
    rows times the next layer's ``W``, flat per head."""
    from repro.core import HaloPrecision, project_store_tables

    cell, gd = _loaded(tiny)
    cfg, params = _gat_config(cell, gd), _gat_params(cell, gd)
    rows = jax.random.normal(jax.random.key(3), (2, 40, 128))
    got = project_store_tables({"data": rows}, params, cfg,
                               HaloPrecision("fp32"))
    want = cell.model.pull(harness.reference_model(cell), list(rows),
                           params, "highest")
    assert sorted(got) == ["z0", "z1"]
    for ell, table in enumerate(want):
        assert table.shape == (40, (128, 7)[ell])
        np.testing.assert_allclose(got[f"z{ell}"]["data"][0], table,
                                   rtol=1e-5, atol=1e-5)


def _gat_config(cell, gd):
    from repro.launch.train_gnn import model_config

    c = cell.config
    return model_config(gd.graph, gd.data, model=c["model"],
                        hidden_dim=c["hidden_dim"],
                        num_layers=c["num_layers"], heads=c["heads"],
                        normalize=c["normalize"])


def _gat_params(cell, gd):
    """A parameter tree of the program's GAT, seeded, not compiled."""
    from repro.models.gnn import gnn_specs
    from repro.nn import init_params

    return init_params(jax.random.PRNGKey(0),
                       gnn_specs(_gat_config(cell, gd)))
