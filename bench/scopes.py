"""From an op's ``op_name`` to the part of the DIGEST epoch it ran in,
and from a traced window to each part's device time.

The program names the parts of its epoch with ``jax.named_scope``
(``repro.core.digest``: ``digest/inputs``, ``digest/pull``,
``digest/loss``, ``digest/opt``, ``digest/push`` with its ``staleness``
probe, ``digest/metrics``; ``repro.models.gnn``: each ``layer_{ell}``'s
``aggregate``, ``transform`` and ``attention``).  The names below are
this module's own copy: a scope renamed in the program shows here as a
rise in ``unscoped`` time, not as a reading that silently follows it.

Time goes to layers by the device's own clock: each instant in which
some op runs on a chip belongs to the innermost op running then (the
latest to start), so a conditional's event and the branch ops nested in
it count once, and the layers' times add up to the busy time.  An op
with no ``op_name`` (one a compiler pass made, as a layout copy) takes
the layer of the innermost named op whose interval holds it (a branch
op of a conditional takes the conditional's), or else of the named ops
its own interval holds, where they all have one; otherwise it is
``unscoped``.
"""
from __future__ import annotations

import collections
import heapq
import re

from bench import compare

UNSCOPED = "unscoped"
# (enclosing scope, scope) -> layer.
_SCOPES = {("digest", "inputs"): "inputs",
           ("digest", "pull"): "pull",
           ("digest", "loss"): "loss",
           ("digest", "opt"): "opt",
           ("digest", "push"): "push",
           ("push", "staleness"): "staleness",
           ("digest", "metrics"): "metrics"}
_LAYER = re.compile(r"^layer_\d+$")
_LAYER_PARTS = ("aggregate", "transform", "attention")
LAYERS = tuple(_SCOPES.values()) + _LAYER_PARTS
# Transformations JAX wraps around the scopes entered inside them, as in
# ``vmap(transpose(jvp(layer_1)))/aggregate``.
_WRAPPER = re.compile(r"(?:vmap|jvp|transpose|remat|checkpoint)\(")


def _unwrap(op_name: str) -> str:
    """``op_name`` with every transformation wrapper taken off, its
    closing parenthesis too; ``jit(...)`` and other names stay."""
    out, kept, i = [], [], 0
    while i < len(op_name):
        m = _WRAPPER.match(op_name, i)
        if m and (i == 0 or op_name[i - 1] in "/("):
            kept.append(False)
            i = m.end()
            continue
        c = op_name[i]
        if c == "(":
            kept.append(True)
        elif c == ")" and kept and not kept.pop():
            i += 1
            continue
        out.append(c)
        i += 1
    return "".join(out)


def layer(op_name: str) -> str:
    """The innermost program scope of an ``op_name``, or ``unscoped``."""
    parts = [p for p in _unwrap(op_name).split("/") if p]
    found = UNSCOPED
    for outer, inner in zip(parts, parts[1:]):
        if (outer, inner) in _SCOPES:
            found = _SCOPES[outer, inner]
        elif inner in _LAYER_PARTS and _LAYER.match(outer):
            found = inner
    return found


def _labels(events: list, op_name) -> list:
    """The layer of each of one chip's events ``(op, start, duration)``,
    sorted by start (an enclosing event before the events it holds)."""
    named = [layer(op_name(n)) if op_name(n) else None
             for n, _, _ in events]
    out, around = [], []        # (end, layer) of named events around
    for i, (_, a, dur) in enumerate(events):
        end = a + dur
        while around and around[-1][0] <= a:
            around.pop()
        if named[i] is not None:
            out.append(named[i])
            around.append((end, named[i]))
            continue
        if around:
            out.append(around[-1][1])
            continue
        inside = set()
        for j in range(i + 1, len(events)):
            _, b, d = events[j]
            if b >= end:
                break
            if b + d <= end and named[j] is not None:
                inside.add(named[j])
        out.append(inside.pop() if len(inside) == 1 else UNSCOPED)
    return out


def chip_ns(events: list, op_name, lo: int, hi: int) -> collections.Counter:
    """One chip's busy ns in [lo, hi) by layer, each instant given to the
    innermost op running then.  ``events`` are ``(op, start, duration)``;
    ``op_name(op)`` is the op's metadata, empty where it has none."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    labels = _labels(events, op_name)
    spans = [(max(a, lo), min(a + d, hi), lab)
             for (_, a, d), lab in zip(events, labels)
             if min(a + d, hi) > max(a, lo)]
    cuts = sorted({t for a, b, _ in spans for t in (a, b)})
    out = collections.Counter()
    running, k = [], 0          # heap of (-start, end, layer)
    for t, nxt in zip(cuts, cuts[1:]):
        while k < len(spans) and spans[k][0] <= t:
            a, b, lab = spans[k]
            heapq.heappush(running, (-a, b, lab))
            k += 1
        while running and running[0][1] <= t:
            heapq.heappop(running)
        if running:
            out[running[0][2]] += nxt - t
    return out


def layer_ms(run) -> dict | None:
    """Device ms in the traced window by layer, per chip of the cell;
    ``None`` without a trace, or where no op lies in a program scope (a
    program that names none)."""
    view = run.trace
    if view is None:
        return None
    total = collections.Counter()
    for events in view.trace.devices.values():
        total.update(chip_ns(events, view.op_name, view.lo, view.hi))
    if not any(total[k] for k in LAYERS):
        return None
    return {k: total[k] / run.chips / 1e6 for k in LAYERS + (UNSCOPED,)}


def window_epochs(run) -> range:
    """The epochs of the traced window: it opens on the first pull of
    pushed rows (``compare.pull_epoch``)."""
    first = compare.pull_epoch(run.cell.traffic["sync_interval"])
    return range(first, first + run.epochs)


def pulls(run) -> int:
    """Pulls in the window: epochs r with r % interval == 0."""
    n = run.cell.traffic["sync_interval"]
    return sum(r % n == 0 for r in window_epochs(run))


def pushes(run) -> int:
    """Pushes in the window: epochs r with (r - 1) % interval == 0."""
    n = run.cell.traffic["sync_interval"]
    return sum((r - 1) % n == 0 for r in window_epochs(run))
