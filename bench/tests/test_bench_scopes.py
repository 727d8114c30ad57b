"""The epoch's named scopes and their reduction (``bench/scopes.py``):
every heavy op of the compiled epoch lies in a program scope, on one
device and on four; the readers of ``pull_ms``, ``push_ms``,
``dense_ms``, ``opt_ms`` and ``unscoped_ms`` on hand-made traces; and
the reduction of a tiny epoch's trace recorded on a TPU chip
(``bench/testdata/record_epoch_trace.py``)."""
import dataclasses
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import cell as cell_mod  # noqa: E402
from bench import graphs, harness, scopes, spec  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from test_bench_cell import make_tiny_root  # noqa: E402

DATA = REPO / "bench" / "testdata"
CELL = "gcn-products120k-n10"
HEAVY = ("fusion", "conditional", "gather", "scatter", "sort", "dot",
         "custom-call", "all-to-all", "all-reduce")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def epoch_ops(hlo: str) -> list:
    """(HLO op, opcode, op_name) of each instruction of the compiled
    epoch whose op_name lies under ``jit(epoch_fn)``."""
    out = []
    for line in hlo.splitlines():
        m, name = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if not (m and name and name.group(1).startswith("jit(epoch_fn)")):
            continue
        code = _OPCODE.search(m.group(2))
        out.append((m.group(1), code.group(1) if code else "",
                    name.group(1)))
    return out


def check_coverage(hlo: str, collective: bool) -> None:
    ops = epoch_ops(hlo)
    heavy = [(n, c, o) for n, c, o in ops
             if c.removesuffix("-start").removesuffix("-done") in HEAVY]
    assert heavy
    outside = [(n, c, o) for n, c, o in heavy
               if scopes.layer(o) == scopes.UNSCOPED]
    assert not outside, outside[:10]
    conds = sorted(scopes.layer(o) for _, c, o in ops if c == "conditional")
    assert conds == ["pull", "push"], conds
    agg = [o for _, _, o in ops if tr.is_aggregation(o)]
    assert agg and {scopes.layer(o) for o in agg} == {"aggregate"}
    a2a = [o for _, c, o in ops if "all-to-all" in c]
    assert bool(a2a) is collective
    assert {scopes.layer(o) for o in a2a} <= {"pull"}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("scopes"))


def test_every_heavy_op_of_the_epoch_lies_in_a_scope(tiny):
    """One device, interval 1 (both conds live), gather pull."""
    cell = spec.load_cell(tiny, "gcn-products120k-n1")
    gd = graphs.load(tiny, cell.graph, cell.config["num_parts"],
                     tiny / "cache")
    hlo = cell_mod.build(cell, gd, 3, jax.devices()[:1]).hlo
    check_coverage(hlo, collective=False)


FOUR_DEVICES = textwrap.dedent("""
    import sys
    from pathlib import Path
    sys.path[:0] = [{repo!r}, {src!r}]
    import jax
    from bench import cell, graphs, spec
    root = Path(sys.argv[1])
    c = spec.load_cell(root, "gcn-products120k-n1-c4")
    gd = graphs.load(root, c.graph, c.config["num_parts"], root / "cache")
    Path(sys.argv[2]).write_text(cell.build(c, gd, 3, jax.devices()[:4]).hlo)
""")


def test_every_heavy_op_lies_in_a_scope_on_four_devices(tiny, tmp_path):
    """Four virtual devices, interval 1, the collective pull: its
    all-to-all lies in the pull."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = tmp_path / "epoch.hlo.txt"
    res = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES.format(
            repo=str(REPO), src=str(REPO / "src")), str(tiny), str(out)],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    check_coverage(out.read_text(), collective=True)


@pytest.mark.parametrize("op_name,want", [
    ("jit(epoch_fn)/digest/inputs/gather", "inputs"),
    ("jit(epoch_fn)/digest/pull/cond/branch_1_fun/gather", "pull"),
    ("jit(epoch_fn)/digest/loss/vmap(jvp(layer_0))/aggregate/jit(spmm)/"
     "jit(halo_spmm)/jit(halo_spmm_stream_pallas)/pallas_call", "aggregate"),
    ("jit(epoch_fn)/digest/loss/vmap(transpose(jvp(layer_2)))/transform/"
     "dot_general", "transform"),
    ("jit(epoch_fn)/digest/loss/layer_1/vmap(transpose(jvp(transform)))/"
     "dot_general", "transform"),
    ("jit(epoch_fn)/digest/loss/vmap(jvp(layer_1))/attention/exp",
     "attention"),
    ("jit(epoch_fn)/digest/loss/vmap(transpose(jvp(jit(take_along_axis))))/"
     "scatter-add", "loss"),
    ("jit(epoch_fn)/digest/opt/sqrt", "opt"),
    ("jit(epoch_fn)/digest/push/cond/branch_1_fun/scatter", "push"),
    ("jit(epoch_fn)/digest/push/staleness/jit(norm)/sqrt", "staleness"),
    ("jit(epoch_fn)/digest/metrics/reduce_sum", "metrics"),
    ("jit(epoch_fn)/vmap(jvp(layer_0))/jit(spmm)/jit(halo_spmm)/add",
     "unscoped"),
    ("jit(epoch_fn)/digest/loss/vmap(jvp(layer_0))/transform_x/dot_general",
     "loss"),
    ("jit(epoch_fn)/pull/gather", "unscoped"),
    ("reduce_sum", "unscoped"),
])
def test_layer_of_an_op_name(op_name, want):
    assert scopes.layer(op_name) == want


OPS = {"c.1": "jit(epoch_fn)/digest/pull/cond",
       "g.2": "jit(epoch_fn)/digest/pull/cond/branch_1_fun/gather",
       "k.3": "jit(epoch_fn)/digest/loss/vmap(jvp(layer_0))/aggregate/"
              "jit(halo_spmm)/pallas_call",
       "d.4": "jit(epoch_fn)/digest/loss/vmap(transpose(jvp(layer_0)))/"
              "transform/dot_general",
       "s.5": "jit(epoch_fn)/digest/push/cond/branch_1_fun/scatter",
       "e.6": "jit(epoch_fn)/digest/push/staleness/sqrt",
       "o.7": "jit(epoch_fn)/digest/opt/add",
       "u.8": "jit(epoch_fn)/add"}


def hand_run(events, interval=10, epochs=2):
    """A run of one chip whose window [0, 100) holds ``events``."""
    cell = spec.load_cell(REPO, CELL)
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, sync_interval=interval))
    trace = tr.Trace(devices={"/device:TPU:0": events},
                     host=[("bench/window", 0, 100)])
    view = harness.TraceView(trace=trace, op_names=OPS, lo=0, hi=100)
    return harness.Run(cell=cell, chips=1, epochs=epochs, window_s=1e-7,
                       setup={}, counts={}, peak=None, trace=view)


def read(name, run):
    return spec.load_reader(REPO, name)(run)


def test_a_conditional_and_its_branch_ops_count_once():
    run = hand_run([("c.1", 0, 40), ("g.2", 5, 20), ("g.2", 25, 10)])
    assert scopes.layer_ms(run)["pull"] == pytest.approx(40 / 1e6)
    assert read("pull_ms", run) == pytest.approx(40 / 1e6)


def test_an_op_with_no_op_name_takes_the_layer_it_encloses():
    run = hand_run([("cond.9.clone_cond", 0, 30), ("g.2", 2, 10),
                    ("g.2", 15, 10), ("sort.1", 40, 10),
                    ("k.3", 50, 10)])
    ms = scopes.layer_ms(run)
    assert ms["pull"] == pytest.approx(30 / 1e6)
    assert ms[scopes.UNSCOPED] == pytest.approx(10 / 1e6)
    mixed = hand_run([("cond.9", 0, 30), ("g.2", 2, 10), ("s.5", 15, 10)])
    ms = scopes.layer_ms(mixed)
    assert (ms["pull"], ms["push"]) == (10 / 1e6, 10 / 1e6)
    assert ms[scopes.UNSCOPED] == pytest.approx(10 / 1e6)


def test_an_op_with_no_op_name_inside_a_named_op_takes_its_layer():
    """A layout copy in a conditional's branch is the conditional's."""
    run = hand_run([("c.1", 0, 40), ("copy.48", 2, 5), ("g.2", 10, 20),
                    ("copy.89", 50, 10)])
    ms = scopes.layer_ms(run)
    assert ms["pull"] == pytest.approx(40 / 1e6)
    assert ms[scopes.UNSCOPED] == pytest.approx(10 / 1e6)


def test_window_epochs_hold_one_pull_and_one_push_at_interval_10():
    run = hand_run([("g.2", 0, 10), ("s.5", 20, 4), ("e.6", 24, 6)])
    assert list(scopes.window_epochs(run)) == [10, 11]
    assert (scopes.pulls(run), scopes.pushes(run)) == (1, 1)
    assert read("pull_ms", run) == pytest.approx(10 / 1e6)
    # The staleness probe is the push's child: not in its self time.
    assert read("push_ms", run) == pytest.approx(4 / 1e6)
    at_one = hand_run([("g.2", 0, 10)], interval=1, epochs=3)
    assert list(scopes.window_epochs(at_one)) == [2, 3, 4]
    assert (scopes.pulls(at_one), scopes.pushes(at_one)) == (3, 3)


def test_a_window_with_no_pull_gives_none(monkeypatch):
    """A window of epochs 2-3 at interval 10 holds neither."""
    run = hand_run([("g.2", 0, 10), ("o.7", 20, 4)])
    monkeypatch.setattr(scopes, "window_epochs", lambda run: range(2, 4))
    assert (scopes.pulls(run), scopes.pushes(run)) == (0, 0)
    assert read("pull_ms", run) is None
    assert read("push_ms", run) is None
    assert read("opt_ms", run) == pytest.approx(4 / 1e6 / 2)


def test_layers_add_up_to_the_busy_time():
    events = [("g.2", 0, 10), ("k.3", 12, 30), ("d.4", 45, 5),
              ("o.7", 50, 5), ("s.5", 60, 5), ("e.6", 66, 3),
              ("u.8", 70, 2), ("sort.1", 80, 30)]      # clipped at 100
    run = hand_run(events, interval=10, epochs=2)
    ms = scopes.layer_ms(run)
    busy = tr.busy_ns(run.trace.trace, 0, 100)["/device:TPU:0"]
    assert sum(ms.values()) == pytest.approx(busy / 1e6)
    assert ms[scopes.UNSCOPED] == pytest.approx(22 / 1e6)
    total = (scopes.pulls(run) * read("pull_ms", run)
             + scopes.pushes(run) * read("push_ms", run)
             + run.epochs * (read("dense_ms", run) + read("opt_ms", run)
                             + read("unscoped_ms", run))
             + ms["aggregate"] + ms["staleness"])
    assert total == pytest.approx(busy / 1e6)


def test_a_program_without_scopes_or_a_trace_reads_nothing():
    run = hand_run([("u.8", 0, 10), ("sort.1", 10, 10)])
    names = ("pull_ms", "push_ms", "dense_ms", "opt_ms", "unscoped_ms")
    assert [read(n, run) for n in names] == [None] * len(names)
    untraced = dataclasses.replace(run, trace=None)
    assert [read(n, untraced) for n in names] == [None] * len(names)


@pytest.fixture(scope="module")
def recorded():
    """The tiny epoch's window on one v5e, at interval 1: epochs 2-3,
    each pulling and pushing."""
    trace = tr.read_xplane(DATA / "epoch_trace.xplane.pb")
    hlo = (DATA / "epoch_trace.hlo.txt").read_text()
    lo, hi = tr.span(trace, "bench/window")
    view = harness.TraceView(trace=trace, op_names=tr.hlo_op_names(hlo),
                             lo=lo, hi=hi)
    cell = spec.load_cell(REPO, CELL)
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                  sync_interval=1))
    run = harness.Run(cell=cell, chips=1, epochs=2, window_s=0.0,
                      setup={}, counts={}, peak=None, trace=view)
    return run, hlo


def test_recorded_epoch_splits_into_the_program_layers(recorded):
    run, _ = recorded
    ms = scopes.layer_ms(run)
    for name in ("pull", "push", "transform", "opt", "aggregate"):
        assert ms[name] > 0, name
    busy_ms = run.trace.busy_s() * 1e3
    assert sum(ms.values()) == pytest.approx(busy_ms)
    assert ms[scopes.UNSCOPED] < 0.02 * busy_ms
    total = (scopes.pulls(run) * read("pull_ms", run)
             + scopes.pushes(run) * read("push_ms", run)
             + run.epochs * (read("dense_ms", run) + read("opt_ms", run)
                             + read("unscoped_ms", run))
             + sum(ms[k] for k in ("inputs", "loss", "aggregate",
                                   "attention", "staleness", "metrics")))
    assert total == pytest.approx(busy_ms)


def test_recorded_chip_op_names_resolve(recorded):
    """The TPU's own op names: the Pallas kernels' custom calls lie in
    ``aggregate``, ops cloned by the compiler in their layer, and each
    op with no op_name takes a layer by the enclosing rule."""
    run, hlo = recorded
    view = run.trace
    kernels = {m.group(1) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)}
    events = sorted(view.trace.devices["/device:TPU:0"],
                    key=lambda e: (e[1], -e[2]))
    labels = dict(zip([e[0] for e in events],
                      scopes._labels(events, view.op_name)))
    assert kernels and kernels <= set(labels)
    assert {labels[k] for k in kernels} == {"aggregate"}
    clones = {n: labels[n] for n in labels if ".clone" in n}
    assert clones and "pull" in clones.values()
    assert set(clones.values()) <= set(scopes.LAYERS)
