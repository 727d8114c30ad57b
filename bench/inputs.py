"""Node features and weights from ``--seed``, made on the device in one
jitted call.

Features are class centres plus Gaussian noise (the recipe's ``noise``),
as the repo's generators draw them, so training behaves like training.
Weights are drawn per leaf of the program's parameter tree, scaled by
fan-in; biases are drawn too (not zeros), so the bias path is compared.
The seed enters as two 32-bit words, so seeds above 2**32 stay distinct
and a new seed does not recompile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _scale(name: str, shape: tuple) -> float:
    if name == "b":
        return 0.1
    if name in ("a_src", "a_dst"):
        return 1.0 / np.sqrt(shape[-1])
    return 1.0 / np.sqrt(shape[0])


@functools.partial(jax.jit, static_argnames=("leaves", "feature_dim",
                                             "num_classes", "noise"))
def _make(lo, hi, labels, *, leaves, feature_dim, num_classes, noise):
    key = jax.random.fold_in(jax.random.key(lo), hi)
    k_centre, k_noise, k_params = jax.random.split(key, 3)
    centres = jax.random.normal(k_centre, (num_classes, feature_dim))
    centres = centres / jnp.linalg.norm(centres, axis=1, keepdims=True)
    x = centres[labels] + noise * jax.random.normal(
        k_noise, (labels.shape[0], feature_dim))
    x_global = jnp.concatenate([x, jnp.zeros((1, feature_dim))], axis=0)
    keys = jax.random.split(k_params, len(leaves))
    params = [_scale(name, shape) * jax.random.normal(k, shape)
              for k, (name, shape) in zip(keys, leaves)]
    return x_global, params


def make(seed: int, labels: np.ndarray, recipe: dict, like):
    """``(x_global, params)``: the ``(N+1, d)`` feature table with the
    program's zero sentinel row last, and a parameter tree shaped like
    ``like`` (the program's own)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = tuple((path[-1].key, tuple(leaf.shape)) for path, leaf in flat)
    seed = int(seed)
    x_global, params = _make(
        np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF),
        jnp.asarray(labels), leaves=leaves,
        feature_dim=recipe["feature_dim"],
        num_classes=recipe["num_classes"], noise=float(recipe["noise"]))
    return x_global, jax.tree_util.tree_unflatten(treedef, params)
