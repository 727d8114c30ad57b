"""The GAT (Velickovic et al., ICLR 2018; arXiv 1710.10903) as DIGEST
trains it (arXiv 2206.00057, section 5): each layer projects every node
with a per-head ``W``, scores each edge (s, d) per head as
``leaky_relu(a_dst . z_d + a_src . z_s, 0.2)``, takes one softmax per node
and head over its in-part edges (its self-loop among them) and its halo
edges together, and sums the neighbours' projected rows with those
weights, plus a bias.  Hidden layers have ``heads`` heads of
``hidden / heads``; the last layer one head as wide as the class count.

What a pull puts in the halo tables, as the program's default
(``gat_halo_dedup``) does: each hidden layer's stored rows projected with
the ``W`` of the layer that reads them, at the pull; the scores read
them with this epoch's ``a_src``, and no gradient flows into ``W``
through them.  Layer 0's halo rows are the features, projected with this
epoch's ``W``.  The departures from arXiv 1710.10903 are listed under
``assumed`` in ``bench/configs/digest-gat.json``.

A model file (see ``bench/models/gcn.py``): ``layer``, ``pull``, ``work``
and ``slab``.  Like ``reference.py`` it imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.reference import edge_sum

SLOPE = 0.2     # leaky_relu's negative slope in the scores


def _project(h, w, prec):
    """(n, din) rows times a (din, heads, dh) ``W``: (n, heads, dh)."""
    return jnp.einsum("nd,dhk->nhk", h, w.astype(h.dtype), precision=prec)


def _scores(e: dict, s_dst, s_src):
    """Per edge of the chunked set ``e`` and head: its score, and whether
    the edge is real (padding edges carry weight 0)."""
    real = (e["w"] > 0)[..., None]
    score = jax.nn.leaky_relu(s_dst[e["dst"]] + s_src[e["src"]], SLOPE)
    return score, real


def layer(model, p, h, tab, g, n, prec):
    """Layer ``p`` over all ``n`` nodes: the edges within a part read this
    epoch's projection of ``h``, those across parts the projected stale
    halo table ``tab`` (``None`` at layer 0, whose halo rows are the
    features ``h`` themselves)."""
    heads, dh = p["a_src"].shape
    ein, eout = g["edges"]["in"], g["edges"]["out"]
    z = _project(h, p["w"], prec)
    z_out = z if tab is None else tab.reshape(n, heads, dh)
    a_src, a_dst = (p[k].astype(h.dtype) for k in ("a_src", "a_dst"))
    head_dot = lambda x, a: jnp.einsum("nhk,hk->nh", x, a, precision=prec)
    s_dst = head_dot(z, a_dst)
    e_in, r_in = _scores(ein, s_dst, head_dot(z, a_src))
    e_out, r_out = _scores(eout, s_dst, head_dot(z_out, a_src))
    # One softmax over both edge sets: their joint maximum per node and
    # head, and their joint sum.
    low = jnp.finfo(h.dtype).min
    m = jnp.full((n, heads), low, h.dtype)
    for e, sc, r in ((ein, e_in, r_in), (eout, e_out, r_out)):
        m = m.at[e["dst"]].max(jnp.where(r, sc, low))
    m = jax.lax.stop_gradient(m)
    # A padding edge's score is never exponentiated: its exp could
    # overflow, and the gradient through the mask would then be 0 * inf.
    weight = lambda e, sc, r: jnp.where(
        r, jnp.exp(jnp.where(r, sc - m[e["dst"]], 0)), 0)
    w_in, w_out = weight(ein, e_in, r_in), weight(eout, e_out, r_out)
    denom = jnp.zeros((n, heads), h.dtype)
    for e, w in ((ein, w_in), (eout, w_out)):
        denom = denom.at[e["dst"]].add(w)
    agg = (edge_sum(ein, w_in / denom[ein["dst"]], z, n)
           + edge_sum(eout, w_out / denom[eout["dst"]], z_out, n))
    return agg.reshape(n, heads * dh) + p["b"].astype(h.dtype)


def pull(model, store, params, prec):
    """Each hidden layer's stored rows projected with the ``W`` of the
    layer that reads them, at the pull: ``(n, heads * dh)`` per layer."""
    out = []
    for ell, rows in enumerate(store):
        z = _project(rows, params[f"layer_{ell + 1}"]["w"], prec)
        out.append(z.reshape(z.shape[0], -1))
    return out


def _aggregation(nnz: int, heads: int, width: int, out_rows: int,
                 table_rows: int, passes: int) -> tuple[int, int]:
    """(FLOPs, bytes) of a multi-head aggregation call over ``passes``
    passes of the same size: the forward, the attention gradient (one
    product per entry and column, summed per head) and, where one flows,
    the table gradient.  Each pass reads each entry's id and its
    ``heads`` weights (or writes their gradients), each distinct table
    row and each output (gradient) row once."""
    entries = nnz * counts.VALUE * (1 + heads)
    rows = counts.VALUE * width * (out_rows + table_rows)
    return passes * 2 * nnz * width, passes * (entries + rows)


def work(config: dict, in_dim: int, num_classes: int,
         shapes: list[dict]) -> dict:
    """``agg_flops``, ``agg_bytes``, ``dense_flops`` and ``epoch_flops``
    of one epoch over the parts' ``shapes`` (``counts.part_shapes``).

    Aggregation: per layer, the in-part call (its table gradient flows:
    the table is this epoch's projection) and the halo call (its table
    gradient flows at layer 0 only, whose halo rows are projected this
    epoch).  Dense: each layer's projection of the part's rows, forward,
    ``W``'s gradient and the input's gradient above layer 0; layer 0's
    projection of its halo rows, forward and ``W``'s gradient; the scores'
    per-node products ``a . z`` (two of the part's rows, one of the halo
    rows), forward and both gradients (the halo rows' above layer 0 have
    none: they enter under stop_gradient)."""
    layers, hidden = config["num_layers"], config["hidden_dim"]
    agg_f = agg_b = dense = 0
    for p in shapes:
        n, halo = p["rows"], p["halo"]
        for ell in range(layers):
            din = in_dim if ell == 0 else hidden
            width = num_classes if ell == layers - 1 else hidden
            heads = 1 if ell == layers - 1 else config["heads"]
            dense += 2 * n * din * width * (3 if ell > 0 else 2)
            dense += 2 * 2 * n * width * 3
            if ell == 0:
                dense += 2 * halo * din * width * 2
                dense += 2 * halo * width * 3
            else:
                dense += 2 * halo * width * 2
            for nnz, table_rows, grad in ((p["nnz_in"], n, True),
                                          (p["nnz_out"], halo, ell == 0)):
                f, b = _aggregation(nnz, heads, width, n, table_rows,
                                    2 + grad)
                agg_f += f
                agg_b += b
    return {"agg_flops": agg_f, "agg_bytes": agg_b, "dense_flops": dense,
            "epoch_flops": agg_f + dense}


def slab(cache: dict) -> list:
    """Per hidden layer, the (M, H + 1, heads * dh) projected halo rows
    the pull put in the host copy of the program's ``cache``."""
    layers = sorted(int(k[1:]) for k in cache if k[1:].isdigit())
    return [np.asarray(cache[f"z{ell}"])[:, 0] for ell in layers]
