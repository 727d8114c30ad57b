"""Public entry: fused GAT aggregation over DIGEST's split adjacency."""
from __future__ import annotations

import functools

import jax

from repro.kernels.gat_edge.gat_edge import gat_edge_partial_pallas
from repro.kernels.gat_edge.ref import gat_edge_partial_ref, merge_partials

BACKENDS = ("auto", "jnp", "pallas", "pallas_interpret")


@functools.partial(jax.jit, static_argnames=("backend",))
def gat_aggregate(in_nbr, in_valid, out_nbr, out_valid, s_dst,
                  s_src_local, s_src_halo, z_local, z_halo,
                  backend: str = "auto") -> jax.Array:
    """Single-head fused GAT layer aggregation (DIGEST split form).

    z_local/z_halo and s_src_* must include the sentinel row. Returns the
    softmax-normalized aggregation over the union of both edge sets.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown GAT aggregation backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend == "jnp":
        fn = gat_edge_partial_ref
        p_in = fn(in_nbr, in_valid, s_dst, s_src_local, z_local)
        p_out = fn(out_nbr, out_valid, s_dst, s_src_halo, z_halo)
    else:
        interp = backend == "pallas_interpret"
        p_in = gat_edge_partial_pallas(in_nbr, in_valid, s_dst,
                                       s_src_local, z_local,
                                       interpret=interp)
        p_out = gat_edge_partial_pallas(out_nbr, out_valid, s_dst,
                                        s_src_halo, z_halo,
                                        interpret=interp)
    return merge_partials([p_in, p_out])
