"""Pallas TPU kernel: blocked causal flash attention (online softmax).

Used by the transformer prefill path of the assigned architectures.

TPU design:
  * grid = (batch*heads, q_blocks, k_blocks), k dimension sequential
    ("arbitrary") so the online-softmax running state can live in VMEM
    scratch across k steps; q/k tiles are (128, head_dim) MXU-aligned.
  * running max m, normalizer l, and accumulator acc are f32 scratch;
    output written on the final k step.
  * causal masking skips fully-masked k blocks via ``pl.when`` on the block
    index (upper-triangular blocks do no work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
                 sm_scale: float, causal: bool, block_q: int, block_k: int,
                 num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale     # (BQ, hd)
        k = k_ref[0].astype(jnp.float32)                # (BK, hd)
        v = v_ref[0].astype(jnp.float32)                # (BK, hd)
        s = jnp.dot(q, k.T)                             # (BQ, BK)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]                             # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)                 # (BQ, 1)
        l_new = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # Skip blocks strictly above the diagonal.
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_compute)
    else:
        _compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0] = (acc_ref[...] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True,
                           sm_scale: float | None = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K, *,
                           interpret: bool) -> jax.Array:
    """q, k, v: (bh, seq, head_dim) — batch*heads flattened on axis 0."""
    bh, seq, hd = q.shape
    if sm_scale is None:
        sm_scale = hd ** -0.5
    bq = min(block_q, seq)
    bk = min(block_k, seq)
    if seq % bq or seq % bk:
        raise ValueError(f"seq={seq} must divide blocks ({bq},{bk})")
    nq, nk = seq // bq, seq // bk
    grid = (bh, nq, nk)
    kernel = functools.partial(
        _attn_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_k=bk, num_k_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq, hd), q.dtype),
        scratch_shapes=[
            # m, l, acc live across the sequential k grid dimension (VMEM).
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
