"""Plain reference of DIGEST training over an edge list.

Independent of the program: it imports nothing of ``repro`` and takes
only the benchmark's graph (its edge list, labels, train mask), the
node-to-part assignment, and the features and weights that ``inputs.py``
draws from the seed.  This module is the loop every model shares; a
model's own arithmetic is its file ``bench/models/<model>.py``
(``spec.load_model``): ``layer``, one layer over the edge sets below,
and ``pull``, what a pull puts into the halo tables.  It follows the
paper's semantics directly, over all N nodes at once rather than per
padded subgraph:

  * the edge sets hold P = D^-1/2 (A + I) D^-1/2 with global degrees
    (GCN weights, which a model that does not read them ignores);
  * an edge whose endpoints lie in one part reads the neighbour's fresh
    representation; an edge across parts reads the stale halo table of
    that layer (layer 0: the raw features, always fresh);
  * every hidden layer is followed by relu and, with ``normalize``, an
    L2 normalization of each row (Algorithm 1 line 11): its rows are
    what a push stores;
  * epoch r (from 1) pulls the store into the halo tables (the model's
    ``pull``) when r % N == 0 and pushes every hidden layer's rows when
    (r - 1) % N == 0;
  * the loss is the mean over parts of each part's mean cross-entropy
    over its training nodes; the gradient is that loss's gradient, and
    Adam updates the parameters.

Aggregations are scatter-adds over edge chunks (a ``lax.scan``), so the
(edges x width) products never exist whole.  ``dtype`` float32 runs at
``precision="highest"``; bfloat16 is the control (all arrays in bf16).
``fault`` plants one of the faults a training cell can have, so that
their readings can be taken without touching the program.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 19


@dataclasses.dataclass(frozen=True)
class Model:
    model: object       # the model file, bench/models/<name>.py
    heads: int
    num_layers: int
    hidden: int
    num_classes: int
    normalize: bool
    lr: float
    b1: float
    b2: float
    eps: float
    sync_interval: int


def _chunked(a: np.ndarray, fill) -> np.ndarray:
    n = max(-(-len(a) // CHUNK), 1) * CHUNK
    out = np.full(n, fill, a.dtype)
    out[:len(a)] = a
    return out.reshape(-1, CHUNK)


def prepare(edges: np.ndarray, assign: np.ndarray, labels: np.ndarray,
            train_mask: np.ndarray, num_parts: int) -> dict:
    """Device arrays of the graph: ``in``/``out`` edge sets (same part /
    across parts) as (src, dst, gcn weight) in CHUNK-row blocks,
    each node's loss weight, and (host) each node's part."""
    n = len(labels)
    u, v = edges[:, 0].astype(np.int64), edges[:, 1].astype(np.int64)
    loop = np.arange(n)
    src = np.concatenate([v, u, loop])
    dst = np.concatenate([u, v, loop])
    deg = np.bincount(dst, minlength=n).astype(np.float64)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    w = (dinv[src] * dinv[dst]).astype(np.float32)
    same = assign[src] == assign[dst]
    sets = {}
    for side, sel in (("in", same), ("out", ~same)):
        sets[side] = {
            "src": jnp.asarray(_chunked(src[sel].astype(np.int32), 0)),
            "dst": jnp.asarray(_chunked(dst[sel].astype(np.int32), 0)),
            "w": jnp.asarray(_chunked(w[sel], np.float32(0))),
        }
    n_train = np.bincount(assign[train_mask], minlength=num_parts)
    lw = np.where(train_mask, 1.0 / (num_parts * np.maximum(
        n_train[assign], 1)), 0.0).astype(np.float32)
    return {"edges": sets, "labels": jnp.asarray(labels),
            "loss_w": jnp.asarray(lw), "part": assign}


def edge_sum(e: dict, coef, table, n: int):
    """out[d] = sum over edges (s, d) of coef_e * table[s]."""
    def body(acc, blk):
        s, d, c = blk
        rows = table[s]
        c = c.reshape(c.shape + (1,) * (rows.ndim - c.ndim))
        return acc.at[d].add(c.astype(table.dtype) * rows), None

    acc = jnp.zeros((n,) + table.shape[1:], table.dtype)
    return jax.lax.scan(body, acc, (e["src"], e["dst"], coef))[0]


def _forward(model: Model, params, x, cache, g, prec):
    n = x.shape[0]
    h, reps = x, []
    for ell in range(model.num_layers):
        tab = None if ell == 0 else jax.lax.stop_gradient(cache[ell - 1])
        out = model.model.layer(model, params[f"layer_{ell}"], h, tab, g,
                                n, prec)
        if ell < model.num_layers - 1:
            out = jax.nn.relu(out)
            if model.normalize:
                out = out / jnp.maximum(jnp.linalg.norm(
                    out, axis=-1, keepdims=True), 1e-12)
            reps.append(out)
        h = out
    return h, reps


def _loss(model, params, x, cache, g, prec):
    logits, reps = _forward(model, params, x, cache, g, prec)
    logits = logits.astype(jnp.float32)
    gold = jnp.take_along_axis(logits, g["labels"][:, None], axis=1)[:, 0]
    nll = jax.nn.logsumexp(logits, axis=1) - gold
    return jnp.sum(nll * g["loss_w"]), reps


@functools.partial(jax.jit, static_argnames=("model", "prec"))
def _step(model, prec, params, m, v, t, x, cache, g):
    (loss, reps), grads = jax.value_and_grad(
        functools.partial(_loss, model), has_aux=True)(
        params, x, cache, g, prec)
    # Adam's arithmetic runs in float32 on the stored dtype's values, as
    # mixed-precision training does (its constants, 0.999 among them, do
    # not survive rounding to bfloat16).
    f32 = lambda a: a.astype(jnp.float32)
    tt = t.astype(jnp.float32) + 1.0
    m = jax.tree.map(lambda a, b: model.b1 * f32(a)
                     + (1 - model.b1) * f32(b), m, grads)
    v = jax.tree.map(lambda a, b: model.b2 * f32(a)
                     + (1 - model.b2) * f32(b) ** 2, v, grads)

    def upd(p, a, b):
        mh = a / (1 - model.b1 ** tt)
        vh = b / (1 - model.b2 ** tt)
        return (f32(p) - model.lr * mh / (jnp.sqrt(vh) + model.eps)
                ).astype(p.dtype)

    params = jax.tree.map(upd, params, m, v)
    back = lambda tree: jax.tree.map(lambda a, p: a.astype(p.dtype), tree,
                                     params)
    return params, back(m), back(v), loss, grads, reps


def run(model: Model, g: dict, x, params0, epochs: int,
        dtype=jnp.float32, fault: str | None = None) -> dict:
    """Train ``epochs`` epochs from ``params0``.  Returns each epoch's
    loss, the first epoch's gradient, the parameters after epoch 3, the
    store after each push (``stores``, by epoch) and the halo tables
    after each pull (``tables``, by epoch), per node, as float32 numpy.

    ``fault``: ``"unchanged"``, every epoch returns its state unchanged
    (so the first gradient reads back as zero, as from the program's
    untouched Adam moment); ``"half"``, the loss is the mean over the
    first half of the parts only; ``"no_pull"``, the pull is left out.
    """
    prec = ("highest" if dtype == jnp.float32 else "default")
    cast = functools.partial(jax.tree.map, lambda a: jnp.asarray(a, dtype))
    n = x.shape[0]
    x = jnp.asarray(x, dtype)
    part = g["part"]
    g = {k: a for k, a in g.items() if k != "part"}
    if fault == "half":
        half = jnp.asarray(part < (int(part.max()) + 1) // 2)
        g["loss_w"] = jnp.where(half, 2 * g["loss_w"], 0.0)
    params = cast(params0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    hid = range(model.num_layers - 1)
    store = [jnp.zeros((n, model.hidden), dtype) for _ in hid]
    # Before the first pull the halo tables hold what a pull of the
    # empty store would.
    cache = model.model.pull(model, store, params, prec)
    out = {"losses": [], "stores": {}, "tables": {}}
    keep = fault != "unchanged"
    for r in range(1, epochs + 1):
        if r % model.sync_interval == 0:
            if keep and fault != "no_pull":
                cache = model.model.pull(model, store, params, prec)
            out["tables"][r] = [np.asarray(c, np.float32) for c in cache]
        new = _step(model, prec, params, m, v, jnp.asarray(r - 1, dtype),
                    x, cache, g)
        loss, grads, reps = new[3:]
        if keep:
            params, m, v = new[:3]
        if (r - 1) % model.sync_interval == 0:
            if keep:
                store = reps
            out["stores"][r] = [np.asarray(s, np.float32) for s in store]
        out["losses"].append(float(loss))
        if r == 1:
            out["grad1"] = host(grads if keep else
                                jax.tree.map(jnp.zeros_like, grads))
        if r == 3:
            out["params3"] = host(params)
    return out


def host(tree):
    """A tree of float32 numpy arrays."""
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)
