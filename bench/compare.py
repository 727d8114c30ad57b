"""The numbers that decide ``correct``, each held to its own limit.

The program and the reference train from the same features and weights
through the cell's first epochs: 1 to 3, and on to the first pull after
the first push (epoch ``p``: the sync interval, or 2 at interval 1) and
the epoch after it, which trains on the pulled rows.  Then:

  loss1_gap   |loss - ref| / |ref| of epoch 1, which no stale row reaches
  loss_gap    the worst epoch's of the same, epochs 1 to p + 1
  grad_gap    worst leaf's | |g| - |g_ref| | / max(|g_ref leaf|, median
              leaf's |g_ref|), of the first epoch's gradient (the
              program's read back from Adam's first moment, m / (1 - b1))
  change_gap  the same of the parameters' change over epochs 1 to 3,
              over the leaves whose reference gradient is at least
              1e-3 of the median leaf's (a leaf with none moves by
              round-off alone under Adam)
  store_gap   worst layer's |store - ref| / |ref| (Frobenius) over the
              boundary rows after epoch 1's push
  slab_gap    worst row's |row - ref| / max(|ref row|, median ref row
              norm) of the halo tables pulled at epoch p, over every
              part's real halo rows
"""
from __future__ import annotations

import jax
import numpy as np

NUMBERS = ("loss1_gap", "loss_gap", "grad_gap", "change_gap", "store_gap",
           "slab_gap")
SILENT_LEAF = 1e-3


def pull_epoch(sync_interval: int) -> int:
    """The first epoch that pulls rows an earlier epoch pushed."""
    return max(sync_interval, 2)


def last_epoch(sync_interval: int) -> int:
    """The last epoch compared: the one after that pull."""
    return max(3, pull_epoch(sync_interval) + 1)


def _norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64)))
                     for a in jax.tree.leaves(tree)])


def _worst_leaf(got: np.ndarray, want: np.ndarray,
                keep: np.ndarray) -> float:
    floor = np.maximum(want, np.median(want))
    return float(np.max((np.abs(got - want) / floor)[keep]))


def _store_gap(got: list, want: list, store_ids: np.ndarray) -> float:
    n = want[0].shape[0] if want else 0
    valid = store_ids < n
    gaps = []
    for ell, want_rows in enumerate(want):
        w = want_rows[store_ids[valid]].astype(np.float64)
        g = np.asarray(got[ell], np.float64)[valid]
        gaps.append(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))
    return float(max(gaps, default=0.0))


def _slab_gap(got: list, want: list, halo_ids: np.ndarray) -> float:
    """``got`` per hidden layer (M, >= H, width) in the program's halo
    order, ``want`` per layer (N, width) per node; rows matched through
    ``halo_ids`` (M, H), N at padding."""
    n = want[0].shape[0] if want else 0
    valid = halo_ids < n
    gaps, norms = [], []
    for m in range(halo_ids.shape[0]):
        ids = halo_ids[m][valid[m]]
        for ell, table in enumerate(want):
            w = table[ids]
            g = np.asarray(got[ell][m, :halo_ids.shape[1]])[valid[m]]
            gaps.append(np.linalg.norm(g - w, axis=1))
            norms.append(np.linalg.norm(w, axis=1))
    if not gaps:
        return 0.0
    gaps, norms = np.concatenate(gaps), np.concatenate(norms)
    return float(np.max(gaps / np.maximum(norms, np.median(norms))))


def numbers(got: dict, ref: dict, params0, ids: dict,
            sync_interval: int) -> dict:
    """``got`` and ``ref`` each hold ``losses`` (epochs 1 to p + 1),
    ``grad1`` and ``params3`` (trees), ``stores`` (by push epoch, per
    layer; epoch 1 is compared) and ``slab`` / ``tables`` (the halo
    tables pulled at epoch p, per hidden layer): ``got``'s in the
    program's layout (store slots; (M, H, width) halo rows), ``ref``'s
    per node, matched through ``ids``' ``store_ids`` and ``halo_ids``."""
    p = pull_epoch(sync_interval)
    last = last_epoch(sync_interval)
    losses = np.asarray(got["losses"][:last], np.float64)
    want = np.asarray(ref["losses"][:last], np.float64)
    loss = np.abs(losses - want) / np.abs(want)
    g_ref = _norms(ref["grad1"])
    every = np.ones(len(g_ref), bool)
    moved = g_ref >= SILENT_LEAF * np.median(g_ref)
    change = lambda params: jax.tree.map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        params, params0)
    return {
        "loss1_gap": float(loss[0]),
        "loss_gap": float(np.max(loss)),
        "grad_gap": _worst_leaf(_norms(got["grad1"]), g_ref, every),
        "change_gap": _worst_leaf(_norms(change(got["params3"])),
                                  _norms(change(ref["params3"])), moved),
        "store_gap": _store_gap(got["stores"][1], ref["stores"][1],
                                ids["store_ids"]),
        "slab_gap": _slab_gap(got["slab"], ref["tables"][p],
                              ids["halo_ids"]),
        "leaves_left_out": int((~moved).sum()),
    }


def in_program_layout(out: dict, ids: dict, sync_interval: int) -> dict:
    """A reference run's output laid out as the program's: stores in slot
    order, the halo tables pulled at epoch p per part (the control and
    planted faults stand in the program's place this way)."""
    p = pull_epoch(sync_interval)
    tables = out["tables"][p]
    n = tables[0].shape[0] if tables else 0
    slot = np.minimum(ids["store_ids"], max(n - 1, 0))
    halo = np.minimum(ids["halo_ids"], max(n - 1, 0))
    return dict(out, stores={r: [s[slot] for s in st]
                             for r, st in out["stores"].items()},
                slab=[t[halo] for t in tables])


def verdict(nums: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [(name, value, limit)]): every number finite and at most
    its limit."""
    rows = [(k, nums[k], float(limits[k])) for k in NUMBERS if k in limits]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return bool(ok and rows), rows
