"""From a profiler trace to device busy time, per-op sums and overlap.

``read_xplane`` keeps what the metrics need from an ``.xplane.pb``: per
TPU device, the ``XLA Ops`` events (HLO op name, start, duration in ns),
and the benchmark's own host spans (``bench/...`` annotations), on the
profiler's common clock.  ``hlo_op_names`` maps each HLO op name of a
compiled program to its ``op_name`` metadata, which names the jitted
function and scopes an op came from.  Everything else works on that
reduced form.
"""
from __future__ import annotations

import collections
import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench/"
_EVENT_OP = re.compile(r"^%?([\w.\-]+)")
_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                       r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
# Scopes of the neighbour aggregation (repro.kernels.spmm): its kernels,
# the ops around them and the VJP all carry one in their op_name.
AGGREGATION_SCOPES = ("jit(spmm)", "jit(halo_spmm)")
# A collective is known by its HLO opcode; its op name is whatever the
# frontend called it (``psum_invariant.7`` is an all-reduce on a TPU).
_COLLECTIVE_OP = re.compile(
    r"\s(?:all-reduce|all-gather|all-to-all|ragged-all-to-all|"
    r"reduce-scatter|collective-permute|collective-broadcast)"
    r"(?:-start|-done)?\(")


@dataclasses.dataclass
class Trace:
    devices: dict   # plane name -> [(hlo op name, start ns, duration ns)]
    host: list      # [(span name, start ns, duration ns)]


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(str(path)).planes:
        if DEVICE_PLANE.match(plane.name):
            events = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events += [(_op(ev.name), int(ev.start_ns),
                                int(ev.duration_ns)) for ev in line.events]
            devices[plane.name] = sorted(events, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                         for ev in line.events
                         if ev.name.startswith(HOST_PREFIX)]
    return Trace(devices=devices, host=sorted(host, key=lambda e: e[1]))


def _op(event_name: str) -> str:
    """The HLO op name of a device event, which on a TPU is named by the
    op's whole instruction text (``%fusion.3 = f32[...] fusion(...)``)."""
    m = _EVENT_OP.match(event_name)
    return m.group(1) if m else event_name


def hlo_op_names(hlo_text: str) -> dict:
    """HLO op name -> its ``op_name`` metadata, for every op of every
    computation in a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def hlo_collectives(hlo_text: str) -> set:
    """The HLO op names of a compiled program's collectives."""
    out = set()
    for line in hlo_text.splitlines():
        m = _EVENT_OP.match(line.strip().removeprefix("ROOT "))
        if m and _COLLECTIVE_OP.search(line):
            out.add(m.group(1))
    return out


def is_aggregation(op_name: str) -> bool:
    return any(scope in op_name for scope in AGGREGATION_SCOPES)


def span(trace: Trace, name: str) -> tuple[int, int]:
    """(start, end) ns of the first host span called ``name``."""
    for n, start, dur in trace.host:
        if n == name:
            return start, start + dur
    raise KeyError(f"no host span {name!r} in the trace")


def _clip(events, lo: int, hi: int):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def union_ns(intervals) -> int:
    """Length of the union of (start, end) intervals."""
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def busy_ns(trace: Trace, lo: int, hi: int) -> dict:
    """Per device: ns in [lo, hi) during which some op ran."""
    return {dev: union_ns((a, b) for _, a, b in _clip(ev, lo, hi))
            for dev, ev in trace.devices.items()}


def op_ns(trace: Trace, pred, lo: int, hi: int) -> int:
    """Summed duration, over devices, of the ops ``pred(name)`` selects."""
    return sum(b - a for ev in trace.devices.values()
               for name, a, b in _clip(ev, lo, hi) if pred(name))


def exposed_ns(trace: Trace, pred, lo: int, hi: int) -> int:
    """Summed over devices: the time in which an op ``pred`` selects runs
    and no other op does."""
    total = 0
    for ev in trace.devices.values():
        sel, rest = [], []
        for name, a, b in _clip(ev, lo, hi):
            (sel if pred(name) else rest).append((a, b))
        total += union_ns(sel + rest) - union_ns(rest)
    return total


def idle_gaps(trace: Trace, lo: int, hi: int) -> list:
    """Device idle time in [lo, hi), summed over devices and named by the
    innermost benchmark host span around each gap's midpoint, longest
    first: [(span name, ns)]."""
    by_host = collections.Counter()
    for ev in trace.devices.values():
        t = lo
        for _, a, b in sorted(_clip(ev, lo, hi), key=lambda e: e[1]):
            if a > t:
                by_host[_host_at(trace, (t + a) // 2)] += a - t
            t = max(t, b)
        if hi > t:
            by_host[_host_at(trace, (t + hi) // 2)] += hi - t
    return by_host.most_common()


def _host_at(trace: Trace, t: int) -> str:
    inner = None
    for name, start, dur in trace.host:
        if start <= t < start + dur and (inner is None or dur < inner[1]):
            inner = (name, dur)
    return inner[0] if inner else "outside benchmark spans"


def top_ops(trace: Trace, label, lo: int, hi: int, k: int = 10) -> list:
    """The ``k`` labels (``label(op name)``) whose ops took the most
    device time in [lo, hi), summed over devices: [(label, ns)]."""
    by_label = collections.Counter()
    for ev in trace.devices.values():
        for name, a, b in _clip(ev, lo, hi):
            by_label[label(name)] += b - a
    return by_label.most_common(k)
