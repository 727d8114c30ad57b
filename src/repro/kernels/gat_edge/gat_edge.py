"""Pallas TPU kernel: fused GAT edge-softmax + neighbor aggregation.

Computes, for one padded-ELL adjacency structure (one of DIGEST's two —
in-subgraph or out-of-subgraph), the *unnormalized online-softmax partial*:

    e[i,k]  = LeakyReLU(s_dst[i] + s_src[nbr[i,k]])        (masked)
    m[i]    = max_k e[i,k]
    l[i]    = Σ_k exp(e[i,k] − m[i])
    acc[i,:]= Σ_k exp(e[i,k] − m[i]) · z[nbr[i,k], :]

Returning (acc, m, l) instead of the normalized output lets the caller
merge the in-subgraph and stale out-of-subgraph partials exactly (same
online-softmax algebra as flash attention / stale-KV), so the fused kernel
composes with DIGEST's split aggregation without materializing edge
scores in HBM — the GPU implementation's segment-softmax writes e twice.

TPU design: grid (row_blocks, feat_blocks); the degree loop runs online
softmax in registers; gather tables (z stripe, s_src) live in VMEM; m/l
are written once (feature-block 0 owns them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 128
BLOCK_F = 128
NEG_INF = -1e30
LEAKY_SLOPE = 0.2


def _gat_kernel(nbr_ref, valid_ref, sdst_ref, ssrc_ref, z_ref,
                acc_ref, m_ref, l_ref):
    deg = nbr_ref.shape[1]
    j = pl.program_id(1)
    z = z_ref[...]                                   # (n_tab, BF)
    ssrc = ssrc_ref[...]                             # (n_tab,)
    sdst = sdst_ref[...]                             # (BR,)

    def body(k, carry):
        m_prev, l_prev, acc = carry
        idx = nbr_ref[:, k]                          # (BR,)
        sv = jnp.take(ssrc, idx, axis=0)             # (BR,)
        e = sdst + sv
        e = jnp.where(e >= 0, e, LEAKY_SLOPE * e)    # LeakyReLU
        e = jnp.where(valid_ref[:, k], e, NEG_INF)
        m_new = jnp.maximum(m_prev, e)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(e - m_new)
        l_new = alpha * l_prev + p
        rows = jnp.take(z, idx, axis=0).astype(jnp.float32)  # (BR, BF)
        acc = acc * alpha[:, None] + p[:, None] * rows
        return m_new, l_new, acc

    br, bf = acc_ref.shape
    init = (jnp.full((br,), NEG_INF, jnp.float32),
            jnp.zeros((br,), jnp.float32),
            jnp.zeros((br, bf), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, deg, body, init)
    acc_ref[...] = acc

    @pl.when(j == 0)
    def _write_stats():
        m_ref[...] = m
        l_ref[...] = l


@functools.partial(jax.jit, static_argnames=("interpret",))
def gat_edge_partial_pallas(nbr: jax.Array, valid: jax.Array,
                            s_dst: jax.Array, s_src: jax.Array,
                            z: jax.Array, *, interpret: bool
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused partial-softmax aggregation.

    Args:
      nbr:   (rows, deg) int32 indices into z/s_src (sentinel allowed).
      valid: (rows, deg) bool — edge validity mask.
      s_dst: (rows,) f32 destination scores.
      s_src: (n_tab,) f32 source-score table (incl. sentinel row).
      z:     (n_tab, feat) value table (incl. sentinel row).
    Returns:
      (acc (rows, feat) f32, m (rows,) f32, l (rows,) f32).
    """
    rows, deg = nbr.shape
    n_tab, feat = z.shape
    br = min(BLOCK_ROWS, rows)
    bf = min(BLOCK_F, feat)
    if rows % br or feat % bf:
        raise ValueError(f"rows={rows}/feat={feat} must divide ({br},{bf})")
    grid = (rows // br, feat // bf)
    return pl.pallas_call(
        _gat_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, deg), lambda i, j: (i, 0)),
            pl.BlockSpec((br, deg), lambda i, j: (i, 0)),
            pl.BlockSpec((br,), lambda i, j: (i,)),
            pl.BlockSpec((n_tab,), lambda i, j: (0,)),
            pl.BlockSpec((n_tab, bf), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((br, bf), lambda i, j: (i, j)),
            pl.BlockSpec((br,), lambda i, j: (i,)),
            pl.BlockSpec((br,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, feat), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.float32),
        ],
        interpret=interpret,
    )(nbr, valid, s_dst.astype(jnp.float32), s_src.astype(jnp.float32), z)
