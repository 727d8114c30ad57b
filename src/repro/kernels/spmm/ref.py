"""Pure-jnp oracle for the ELL SpMM kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def spmm_ref(nbr: jax.Array, wts: jax.Array, table: jax.Array) -> jax.Array:
    """out[i] = sum_k wts[i,k] * table[nbr[i,k]] — vectorized gather form."""
    gathered = jnp.take(table, nbr, axis=0)        # (rows, deg, feat)
    w = wts.astype(jnp.float32)[..., None]
    return jnp.sum(w * gathered.astype(jnp.float32), axis=1)


def multihead_spmm_ref(nbr: jax.Array, att: jax.Array,
                       table: jax.Array) -> jax.Array:
    """out[i, h·dh:(h+1)·dh] = sum_k att[i,k,h] * table[nbr[i,k],
    h·dh:(h+1)·dh] — per-head weights over a ``(T, heads·dh)`` table,
    vectorized gather form."""
    rows, deg, heads = att.shape
    gathered = jnp.take(table, nbr, axis=0).astype(jnp.float32)
    gathered = gathered.reshape(rows, deg, heads, -1)   # (rows, deg, h, dh)
    w = att.astype(jnp.float32)[..., None]
    return jnp.sum(w * gathered, axis=1).reshape(rows, -1)


def halo_spmm_ref(nbr: jax.Array, wts: jax.Array, data: jax.Array,
                  scale: jax.Array = None, pdata: jax.Array = None,
                  pscale: jax.Array = None,
                  gamma: float = 1.0) -> jax.Array:
    """Fused pull+aggregate oracle: SpMM against a (possibly quantized)
    compact slab with per-row dequant scales folded into the weights.

    With a predictor slab (``pdata``/``pscale`` — the SAT history rows,
    same layout as ``data``/``scale``) each gathered row is the
    staleness-alleviated prediction
    ``dequant(data[s]) + gamma * dequant(pdata[s])``."""
    w = wts.astype(jnp.float32)
    ws = w
    if scale is not None:
        ws = w * jnp.take(scale[:, 0], nbr, axis=0)
    gathered = jnp.take(data, nbr, axis=0).astype(jnp.float32)
    out = jnp.sum(ws[..., None] * gathered, axis=1)
    if pdata is not None:
        wp = w * jnp.float32(gamma)
        if pscale is not None:
            wp = wp * jnp.take(pscale[:, 0], nbr, axis=0)
        pgathered = jnp.take(pdata, nbr, axis=0).astype(jnp.float32)
        out = out + jnp.sum(wp[..., None] * pgathered, axis=1)
    return out


def halo_spmm_skip_ref(nbr: jax.Array, wts: jax.Array, data: jax.Array,
                       scale: jax.Array, wl_ids, wl_cnt,
                       chunk_rows: int, block_rows: int = 128,
                       pdata: jax.Array = None, pscale: jax.Array = None,
                       gamma: float = 1.0) -> jax.Array:
    """Worklist-masked oracle for the chunk-skipping streamed kernel.

    Accumulates only the contributions whose slab row falls inside a
    *visited* chunk of the (row_block × chunk) worklist — so it equals
    :func:`halo_spmm_ref` iff the worklist covers every referenced slot
    (the completeness property the skip kernel's correctness rests on),
    and it diverges loudly on a deliberately truncated worklist."""
    import numpy as np

    rows = nbr.shape[0]
    ids = np.asarray(wl_ids)
    cnt = np.asarray(wl_cnt)
    n_blocks = ids.shape[0]
    n_chunks = max(-(-data.shape[0] // chunk_rows), 1)
    # visited[i, c]: chunk c is on row block i's worklist.
    visited = np.zeros((n_blocks, n_chunks), bool)
    for i in range(n_blocks):
        visited[i, ids[i, :cnt[i]]] = True
    visited = jnp.asarray(visited)
    block_of = jnp.minimum(jnp.arange(rows) // block_rows, n_blocks - 1)
    in_visited = visited[block_of[:, None], nbr // chunk_rows]
    w = wts.astype(jnp.float32) * in_visited.astype(jnp.float32)
    ws = w
    if scale is not None:
        ws = w * jnp.take(scale[:, 0], nbr, axis=0)
    gathered = jnp.take(data, nbr, axis=0).astype(jnp.float32)
    out = jnp.sum(ws[..., None] * gathered, axis=1)
    if pdata is not None:
        wp = w * jnp.float32(gamma)
        if pscale is not None:
            wp = wp * jnp.take(pscale[:, 0], nbr, axis=0)
        pgathered = jnp.take(pdata, nbr, axis=0).astype(jnp.float32)
        out = out + jnp.sum(wp[..., None] * pgathered, axis=1)
    return out
