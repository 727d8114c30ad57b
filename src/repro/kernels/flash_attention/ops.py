"""Jitted public attention entry point with backend dispatch.

``backend="auto"`` → Pallas kernel on TPU, jnp oracle on CPU (same math);
``"pallas_interpret"`` runs the kernel body in the Pallas interpreter and
any other name outside ``BACKENDS`` raises.
Accepts (batch, seq, heads, head_dim) with GQA K/V (fewer kv heads) and
flattens to the kernel's (bh, seq, hd) layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_pallas)
from repro.kernels.flash_attention.ref import attention_ref

BACKENDS = ("auto", "jnp", "pallas", "pallas_interpret")


@functools.partial(jax.jit, static_argnames=("causal", "backend"))
def multi_head_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         causal: bool = True,
                         backend: str = "auto") -> jax.Array:
    """q: (B, S, H, D); k, v: (B, S, KV, D) with H % KV == 0.

    Returns (B, S, H, D).
    """
    b, s, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    if backend not in BACKENDS:
        raise ValueError(f"unknown attention backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if backend == "auto":
        backend = ("pallas" if jax.default_backend() == "tpu" else "jnp")
    if backend == "jnp":
        out = attention_ref(qf, kf, vf, causal=causal)
    else:
        out = flash_attention_pallas(
            qf, kf, vf, causal=causal,
            interpret=backend == "pallas_interpret")
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
