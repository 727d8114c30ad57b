"""The benchmark's files are found by name: a cell, a graph recipe or a
metric is added by adding files and entries, never by editing one.  Also
the checks that keep ``BENCHMARK.json`` within its contract, the peak
table's refusal of an unknown chip, and the command's refusal to run
without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import harness, spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture
def root(tmp_path):
    """A copy of the benchmark's data files (no program)."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(
                        "__pycache__", ".graph_cache", ".traces", "tests"))
    return tmp_path


def test_every_cell_loads_with_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        assert cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(spec.load_reader(REPO, m["name"]))


def test_benchmark_json_keeps_its_contract():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in bench["per_layer"])
    assert all(0 < m["bound"] <= 0.25 for m in bench["end_to_end"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    for c in bench["configs"]:
        assert Path(REPO, c["file"]).exists()
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden|heads)", key), key


def test_a_new_cell_is_found_from_new_files_only(root):
    """A new traffic mix, graph recipe, configuration, limits and metric,
    added as files and entries; no existing file's content changes."""
    bench_dir = root / "bench"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*")
              if p.is_file()}
    (bench_dir / "traffic" / "n5.json").write_text(json.dumps(
        {"sync_interval": 5, "precision": "int8", "pull_mode": "gather"}))
    graph = json.loads((bench_dir / "graphs" / "products-120k.json")
                       .read_text())
    graph.update(name="products-60k", num_nodes=60000)
    (bench_dir / "graphs" / "products-60k.json").write_text(
        json.dumps(graph))
    config = json.loads((bench_dir / "configs" / "digest-gcn.json")
                        .read_text())
    config.update(name="digest-gcn-60k", graph="products-60k",
                  num_nodes=60000)
    (bench_dir / "configs" / "digest-gcn-60k.json").write_text(
        json.dumps(config))
    (bench_dir / "limits" / "gcn-60k-n5.json").write_text(json.dumps(
        {"loss_gap": 1e-3}))
    (bench_dir / "metrics" / "epochs.py").write_text(
        "def read(run):\n    return float(run.epochs)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "digest-gcn-60k",
                             "source": "https://arxiv.org/abs/2206.00057",
                             "file": "bench/configs/digest-gcn-60k.json",
                             "reduced": ["num_nodes"], "why": "test"})
    bench["workloads"].append({"name": "gcn-60k-n5",
                               "config": "digest-gcn-60k",
                               "traffic": "n5", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "epochs", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "epoch", "moves": "epoch_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell(root, "gcn-60k-n5")
    assert cell.traffic["sync_interval"] == 5
    assert cell.graph["num_nodes"] == 60000
    assert cell.config["name"] == "digest-gcn-60k"
    assert cell.limits == {"loss_gap": 1e-3}
    assert "epochs" in [m["name"] for m in cell.per_layer]
    assert spec.load_reader(root, "epochs")(
        harness.Run(cell=cell, chips=1, epochs=3, window_s=1.0, setup={},
                    counts={}, peak=None, trace=None)) == 3.0
    # The old cell is unchanged; it reports the new metric too, which
    # names no workloads and moves a metric the old cell reports.
    old = spec.load_cell(root, "gcn-products120k-n10")
    assert "epochs" in [m["name"] for m in old.per_layer]
    for p, content in before.items():
        assert p.read_bytes() == content, p


def test_missing_cell_and_files_are_errors(root):
    with pytest.raises(KeyError):
        spec.load_cell(root, "no-such-cell")
    (root / "bench" / "limits" / "gcn-products120k-n10.json").unlink()
    with pytest.raises(FileNotFoundError):
        spec.load_cell(root, "gcn-products120k-n10")


def test_peaks_refuse_an_unknown_device_kind():
    assert harness.peaks(REPO, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness.peaks(REPO, "TPU v9 imaginary")


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_refuses_without_a_tpu():
    res = _run(REPO, "--workload", "gcn-products120k-n10", "--seed", "0",
               "--seconds", "10", "--trace", "0")
    assert res.returncode != 0
    assert "no TPU found" in res.stderr
    assert '"metrics"' not in res.stdout


def test_run_refuses_without_the_program(root):
    """A checkout holding only BENCHMARK.json and bench/ cannot run."""
    shutil.copy(REPO / "bench" / "run.py", root / "bench" / "run.py")
    res = _run(root, "--workload", "gcn-products120k-n10", "--seed", "3",
               "--seconds", "10", "--trace", "0")
    assert res.returncode != 0
    assert '"metrics"' not in res.stdout
