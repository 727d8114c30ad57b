"""The trace reduction: busy and idle time, per-op sums, collective
overlap and idle gaps by host span, on hand-built events and on a small
trace recorded on TPU chips (``bench/testdata/record_trace.py``)."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import trace_reduce as tr  # noqa: E402

DATA = REPO / "bench" / "testdata"


def hand_trace():
    """Two devices over the window [0, 100):
    dev0: fusion.1 [0,30)  all-reduce.2 [20,50)  fusion.3 [60,70)
    dev1: all-to-all.4 [10,40)  custom-call.5 [90,120)  (clipped at 100)
    host: window [0,100), dispatch [0,8), block [8,100)."""
    return tr.Trace(
        devices={"/device:TPU:0": [("fusion.1", 0, 30),
                                   ("all-reduce.2", 20, 30),
                                   ("fusion.3", 60, 10)],
                 "/device:TPU:1": [("all-to-all.4", 10, 30),
                                   ("custom-call.5", 90, 30)]},
        host=[("bench/window", 0, 100), ("bench/dispatch", 0, 8),
              ("bench/block", 8, 92)])


def test_union_by_hand():
    assert tr.union_ns([]) == 0
    assert tr.union_ns([(0, 10), (5, 15), (20, 25)]) == 20
    assert tr.union_ns([(0, 10), (2, 3), (10, 12)]) == 12


def test_busy_ops_and_exposure_by_hand():
    t = hand_trace()
    lo, hi = tr.span(t, "bench/window")
    assert (lo, hi) == (0, 100)
    assert tr.busy_ns(t, lo, hi) == {"/device:TPU:0": 60,
                                     "/device:TPU:1": 40}
    coll = {"all-reduce.2", "all-to-all.4"}.__contains__
    assert tr.op_ns(t, coll, lo, hi) == 30 + 30
    assert tr.op_ns(t, lambda n: n.startswith("custom-call"), lo, hi) == 10
    # dev0: all-reduce [20,50) overlaps fusion.1 until 30 -> 20 exposed;
    # dev1: all-to-all runs alone -> 30 exposed.
    assert tr.exposed_ns(t, coll, lo, hi) == 20 + 30


def test_idle_gaps_are_named_by_the_innermost_host_span():
    t = hand_trace()
    gaps = dict(tr.idle_gaps(t, 0, 100))
    # dev0 idle [50,60) and [70,100); dev1 idle [0,10) and [40,90).
    assert gaps == {"bench/block": 10 + 30 + 50, "bench/dispatch": 10}
    assert sum(gaps.values()) == 200 - 60 - 40


def test_top_ops():
    t = hand_trace()
    top = tr.top_ops(t, lambda n: n.split(".")[0], 0, 100, k=2)
    assert top == [("fusion", 40), ("all-reduce", 30)]


def test_hlo_op_names_and_collectives():
    hlo = "\n".join([
        '  %fusion.1 = f32[8]{0:T(8)} fusion(%a), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(epoch_fn)/layer_0/jit(spmm)/mul" '
        'source_file="x.py"}',
        '  ROOT %psum.2 = f32[8] all-reduce(%b), '
        'metadata={op_name="jit(epoch_fn)/psum"}',
        '  %a2a-start = (f32[8], u32[]) all-to-all-start(%c)',
        '  %c = f32[8] parameter(0)'])
    names = tr.hlo_op_names(hlo)
    assert names == {"fusion.1": "jit(epoch_fn)/layer_0/jit(spmm)/mul",
                     "psum.2": "jit(epoch_fn)/psum"}
    assert tr.hlo_collectives(hlo) == {"psum.2", "a2a-start"}
    assert tr.is_aggregation(names["fusion.1"])
    assert not tr.is_aggregation(names["psum.2"])


@pytest.fixture(scope="module")
def recorded():
    return tr.read_xplane(DATA / "chip_trace.xplane.pb")


def test_recorded_trace_has_the_chips_and_the_host_spans(recorded):
    chips = len(recorded.devices)
    assert 1 <= chips <= 4
    assert sorted(recorded.devices) == [f"/device:TPU:{i}"
                                        for i in range(chips)]
    names = [n for n, _, _ in recorded.host]
    assert names.count("bench/window") == 1
    assert names.count("bench/dispatch") == 3
    lo, hi = tr.span(recorded, "bench/window")
    for events in recorded.devices.values():
        inside = [e for e in events if lo <= e[1] < hi]
        assert inside, "device ops fall inside the host window"


def test_recorded_trace_reduction(recorded):
    lo, hi = tr.span(recorded, "bench/window")
    busy = tr.busy_ns(recorded, lo, hi)
    total = sum(e[2] for ev in recorded.devices.values() for e in ev
                if lo <= e[1] and e[1] + e[2] <= hi)
    for dev, ns in busy.items():
        assert 0 < ns <= hi - lo
    # Every op of the program lies in the window, once per step and chip.
    assert tr.op_ns(recorded, lambda n: True, lo, hi) == total
    hlo = (DATA / "chip_trace.hlo.txt").read_text()
    collectives = tr.hlo_collectives(hlo)
    coll = tr.op_ns(recorded, collectives.__contains__, lo, hi)
    exposed = tr.exposed_ns(recorded, collectives.__contains__, lo, hi)
    assert 0 <= exposed <= coll
    if len(busy) > 1:
        # The program's psum and all_to_all, under their frontend names.
        assert {"psum_invariant.7", "all_to_all.7"} <= collectives
        assert coll > 0
    gaps = tr.idle_gaps(recorded, lo, hi)
    assert sum(ns for _, ns in gaps) == pytest.approx(
        len(busy) * (hi - lo) - sum(busy.values()))
    ops = tr.hlo_op_names(hlo)
    traced = {n for ev in recorded.devices.values() for n, _, _ in ev}
    assert traced & set(ops), "trace events carry the HLO op names"
