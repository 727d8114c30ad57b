"""``bench/counts.py`` against hand counts on a tiny partitioned graph."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import counts, spec  # noqa: E402


def tiny_data():
    """Two parts of S = 4 rows (3 and 2 real), halo H = 3 (2 and 1 real).
    Part 0: in-ELL entries 3 + 2 + 1, out-ELL entries 1 + 1; part 1:
    in-ELL 2 + 1, out-ELL 1 + 0."""
    s, h = 4, 3
    in_nbr = np.full((2, s, 3), s, np.int32)
    in_nbr[0, 0] = [0, 1, 2]
    in_nbr[0, 1, :2] = [0, 1]
    in_nbr[0, 2, :1] = [2]
    in_nbr[1, 0, :2] = [0, 1]
    in_nbr[1, 1, :1] = [1]
    out_nbr = np.full((2, s, 2), h, np.int32)
    out_nbr[0, 0, 0] = 0
    out_nbr[0, 2, 0] = 1
    out_nbr[1, 1, 0] = 0
    valid = np.zeros((2, s), bool)
    valid[0, :3] = True
    valid[1, :2] = True
    halo_valid = np.zeros((2, h), bool)
    halo_valid[0, :2] = True
    halo_valid[1, :1] = True
    return {"local_ids": np.zeros((2, s), np.int32),
            "halo_ids": np.zeros((2, h), np.int32),
            "local_valid": valid, "halo_valid": halo_valid,
            "struct": {"in_nbr": in_nbr, "out_nbr": out_nbr}}


def test_part_shapes_count_real_entries_only():
    assert counts.part_shapes(tiny_data()) == [
        {"rows": 3, "halo": 2, "nnz_in": 6, "nnz_out": 2},
        {"rows": 2, "halo": 1, "nnz_in": 3, "nnz_out": 1}]


def test_ell_fill():
    fill = counts.ell_fill(tiny_data())
    assert fill["in"] == pytest.approx(9 / (5 * 3))
    assert fill["out"] == pytest.approx(3 / (5 * 2))


def test_aggregation_by_hand():
    # 6 entries of width 8 into 3 rows from a 4-row table.
    f, b = counts.aggregation(6, 8, 3, 4)
    assert f == 2 * 6 * 8
    assert b == 6 * 8 + 4 * 8 * (3 + 4)
    f2, b2 = counts.aggregation(6, 8, 3, 4, grad_table=True)
    assert (f2, b2) == (2 * f, 2 * b)


@pytest.mark.parametrize("model", ["gcn", "nomodel"])
def test_epoch_by_hand(model, tmp_path):
    cfg = {"model": model, "num_layers": 2, "hidden_dim": 8, "heads": 2}
    if model != "gcn":
        # A model with no file under bench/models is refused, not counted
        # as a GCN: by the count, and when a cell naming it is loaded.
        missing = "bench/models/nomodel.py"
        with pytest.raises(FileNotFoundError, match=missing):
            counts.epoch(cfg, in_dim=5, num_classes=3, data=tiny_data())
        shutil.copy(REPO / "BENCHMARK.json", tmp_path)
        shutil.copytree(REPO / "bench", tmp_path / "bench",
                        ignore=shutil.ignore_patterns(
                            "__pycache__", ".graph_cache", ".traces",
                            "tests", "testdata"))
        path = tmp_path / "bench" / "configs" / "digest-gcn.json"
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        model=model)))
        with pytest.raises(FileNotFoundError, match=missing):
            spec.load_cell(tmp_path, "gcn-products120k-n10")
        return
    got = counts.epoch(cfg, in_dim=5, num_classes=3, data=tiny_data())
    agg = dense = 0
    for n, nin, nout in ((3, 6, 2), (2, 3, 1)):
        # layer 0: width 5, no table gradient on either side;
        # layer 1: width 8, table gradient on the local side only.
        agg += 2 * nin * 5 + 2 * nout * 5
        agg += 2 * 2 * nin * 8 + 2 * nout * 8
        dense += 2 * n * 5 * 8 * 2 + 2 * n * 8 * 3 * 3
    assert got["agg_flops"] == agg
    assert got["dense_flops"] == dense
    assert got["epoch_flops"] == agg + dense
    assert got["agg_bytes"] > 0
