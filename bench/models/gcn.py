"""The GCN (Kipf and Welling, ICLR 2017; arXiv 1609.02907) as DIGEST
trains it: a layer is P h W + b, with P the normalized adjacency that
``reference.prepare``'s edge sets carry.

A model file, found by ``spec.load_model`` from a configuration's
``"model"``, gives the benchmark four things; like ``reference.py`` it
imports nothing of the program:

  layer(model, p, h, tab, g, n, prec)    one layer of the plain reference
  pull(model, store, params, prec)       what the reference's pull puts
                                         into its halo tables
  work(config, in_dim, num_classes, shapes)
                                         the epoch's required work
                                         (``counts.epoch``)
  slab(cache)                            the program's pulled halo tables,
                                         as ``compare`` takes them
"""
from __future__ import annotations

import jax.numpy as jnp

from bench import counts
from bench.reference import edge_sum


def layer(model, p, h, tab, g, n, prec):
    """Layer ``p`` over all ``n`` nodes: the edges within a part read
    ``h``, those across parts the stale halo table ``tab`` (``None`` at
    layer 0, whose halo rows are the features ``h`` themselves)."""
    ein, eout = g["edges"]["in"], g["edges"]["out"]
    agg = (edge_sum(ein, ein["w"], h, n)
           + edge_sum(eout, eout["w"], h if tab is None else tab, n))
    return jnp.matmul(agg, p["w"].astype(h.dtype), precision=prec) + p["b"]


def pull(model, store, params, prec):
    """A pull puts the store's rows into the halo tables as they are."""
    return list(store)


def work(config: dict, in_dim: int, num_classes: int,
         shapes: list[dict]) -> dict:
    """``agg_flops``, ``agg_bytes``, ``dense_flops`` and ``epoch_flops``
    of one epoch over the parts' ``shapes`` (``counts.part_shapes``)."""
    layers, hidden = config["num_layers"], config["hidden_dim"]
    agg_f = agg_b = dense = 0
    for p in shapes:
        n, halo = p["rows"], p["halo"]
        for ell in range(layers):
            din = in_dim if ell == 0 else hidden
            dout = num_classes if ell == layers - 1 else hidden
            # Forward and weight gradient; the input gradient above
            # layer 0.
            dense += 2 * n * din * dout * (3 if ell > 0 else 2)
            for nnz, table_rows, grad in ((p["nnz_in"], n, ell > 0),
                                          (p["nnz_out"], halo, False)):
                f, b = counts.aggregation(nnz, din, n, table_rows,
                                          grad_table=grad)
                agg_f += f
                agg_b += b
    return {"agg_flops": agg_f, "agg_bytes": agg_b, "dense_flops": dense,
            "epoch_flops": agg_f + dense}


def slab(cache: dict) -> list:
    """Per hidden layer, the (M, H + 1, hidden) halo rows the pull put in
    the host copy of the program's ``cache`` (the raw stored rows)."""
    data = cache["data"]
    return [data[:, ell] for ell in range(data.shape[1])]
