"""Production mesh builders (TPU v5e pod topology).

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False, pods: int = None):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (pods, 16, 16) chips, axes (pod, data, model) — the
    default ``pods=2`` is the 512-chip production dry-run; ``pods``
    overrides the pod count (>1 implies multi-pod)."""
    if pods is None:
        pods = 2 if multi_pod else 1
    if pods < 1 or (multi_pod and pods < 2):
        # A single-pod mesh under --multi-pod would silently validate
        # the wrong program (the census record only names the shape).
        raise ValueError(f"pods={pods} contradicts multi_pod={multi_pod}"
                         f" — multi-pod needs pods >= 2, single-pod "
                         f"exactly pods=1 (or omit pods)")
    if pods > 1:
        return _make_mesh((pods, 16, 16), ("pod", "data", "model"))
    return _make_mesh((16, 16), ("data", "model"))


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1,
                   devices=None):
    """Small mesh over however many (host) devices exist — tests/examples
    — or over ``devices`` (e.g. ``jax.devices()[:1]``, a one-device
    reference beside a larger mesh in the same process)."""
    shape, axes = [], []
    if pod > 1:
        shape.append(pod)
        axes.append("pod")
    shape += [data, model]
    axes += ["data", "model"]
    return _make_mesh(tuple(shape), tuple(axes), devices)


# Hardware constants (TPU v5e) for the roofline report.
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
