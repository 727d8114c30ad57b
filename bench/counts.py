"""Required FLOPs and bytes of one DIGEST epoch, counted from shapes.

The aggregation's required work is counted from the ELL's real entries
(nnz) and the tables' real rows, never from padding or from how a kernel
computes it (the one-hot tiles), so it reads the same whatever
implements it.  Per aggregation call over ``nnz`` entries of ``width``
features:

  forward        2 nnz width FLOPs; bytes: each entry's id and
                 coefficient (4 + 4), each distinct table row once, each
                 output row once (4 B a value)
  table grad     the same again, where a gradient flows into the table

A gradient flows into a table only where it depends on the parameters:
never into the stale halo rows nor the layer-0 features.

``epoch_flops`` adds the dense transforms, forward and backward (the
weight gradient always, the input gradient above layer 0), to the
aggregation.  Elementwise work (activations, normalization, softmax) and
the optimizer are not counted.  All counts are for all parts together,
per epoch without a pull or push.
"""
from __future__ import annotations

import numpy as np

VALUE = 4   # bytes of an fp32 value or an int32 id


def aggregation(nnz: int, width: int, out_rows: int, table_rows: int,
                grad_table: bool = False) -> tuple[int, int]:
    """(FLOPs, bytes) of one aggregation call with its gradient."""
    entries = nnz * VALUE * 2
    rows = VALUE * width * (out_rows + table_rows)
    flops, nbytes = 2 * nnz * width, entries + rows
    if grad_table:
        flops += 2 * nnz * width
        nbytes += entries + rows
    return flops, nbytes


def part_shapes(data: dict) -> list[dict]:
    """Per part: real local rows, halo rows, and in/out ELL entries."""
    s = data["local_ids"].shape[1]
    h = data["halo_ids"].shape[1]
    st = data["struct"]
    return [{"rows": int(data["local_valid"][m].sum()),
             "halo": int(data["halo_valid"][m].sum()),
             "nnz_in": int((st["in_nbr"][m] < s).sum()),
             "nnz_out": int((st["out_nbr"][m] < h).sum())}
            for m in range(data["local_ids"].shape[0])]


def epoch(config: dict, in_dim: int, num_classes: int,
          data: dict) -> dict:
    """``agg_flops``, ``agg_bytes``, ``dense_flops`` and ``epoch_flops``
    of one epoch of the GCN ``config`` over the partitioned ``data``."""
    if config["model"] != "gcn":
        raise ValueError(f"no count for model {config['model']!r}")
    layers, hidden = config["num_layers"], config["hidden_dim"]
    agg_f = agg_b = dense = 0
    for p in part_shapes(data):
        n, halo = p["rows"], p["halo"]
        for ell in range(layers):
            din = in_dim if ell == 0 else hidden
            dout = num_classes if ell == layers - 1 else hidden
            # Forward and weight gradient; the input gradient above
            # layer 0.
            dense += 2 * n * din * dout * (3 if ell > 0 else 2)
            for nnz, table_rows, grad in ((p["nnz_in"], n, ell > 0),
                                          (p["nnz_out"], halo, False)):
                f, b = aggregation(nnz, din, n, table_rows,
                                   grad_table=grad)
                agg_f += f
                agg_b += b
    return {"agg_flops": agg_f, "agg_bytes": agg_b, "dense_flops": dense,
            "epoch_flops": agg_f + dense}


def ell_fill(data: dict) -> dict:
    """Share of ELL slots holding a real entry, in-ELL and out-ELL, over
    the real rows of every part."""
    s = data["local_ids"].shape[1]
    h = data["halo_ids"].shape[1]
    st, valid = data["struct"], np.asarray(data["local_valid"])
    out = {}
    for side, nbr, n_cols in (("in", st["in_nbr"], s),
                              ("out", st["out_nbr"], h)):
        nbr = np.asarray(nbr)
        slots = int(valid.sum()) * nbr.shape[-1]
        out[side] = float((nbr < n_cols).sum()) / max(slots, 1)
    return out
