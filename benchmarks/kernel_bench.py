"""Kernel micro-benchmarks (CPU host timings of the jnp paths; the Pallas
TPU kernels are validated in interpret mode and characterized structurally
in the roofline — wall-clock kernel timing needs real hardware).

The resident-vs-streaming halo_spmm pair runs both Pallas variants in
interpret mode on an identical int8 slab: the numbers are Python-
interpreter timings (not TPU wall clock) but pin the structural cost of
chunking — and, more importantly, that the streaming path handles a slab
several chunks long while the resident path parks it whole in VMEM."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.core import halo_exchange as hx
from repro.graph.generators import community_powerlaw_graph
from repro.graph.partition import build_chunk_worklist, build_partitions
from repro.kernels.flash_attention import multi_head_attention
from repro.kernels.spmm import (halo_spmm_pallas,
                                halo_spmm_skip_pallas,
                                halo_spmm_stream_pallas, spmm)
from repro.models.attention import chunked_attention


def _occupancy_sweep(rng) -> list[dict]:
    """Dense-stream vs chunk-skipping stream on synthetic slabs whose
    (row_block × chunk) occupancy is pinned at 5/25/75%: each 128-row
    block references slots confined to its own random subset of chunks.
    Reports chunks-visited and bytes-streamed next to wall time — the
    structural claim is that the skip stream's DMA traffic follows
    occupancy while the dense stream always pays row_blocks × n_chunks
    chunks (interpret-mode wall clock is Python-loop bound, so the byte
    counts are the hardware-relevant signal)."""
    rows_out, deg, feat, chunk, n_chunks = 512, 8, 128, 128, 16
    ntab = n_chunks * chunk                      # 2048-row int8 slab
    n_blocks = rows_out // 128
    slab = rng.normal(size=(ntab, feat)).astype(np.float32)
    slab[-1] = 0
    data, scale = hx.quantize_rows(jnp.asarray(slab),
                                   hx.HaloPrecision("int8"))
    data = jnp.asarray(np.asarray(data).copy())
    # One streamed chunk tile: int8 stripe + fp32 scale column per row.
    chunk_bytes = chunk * (feat * 1 + 4)
    wts = jnp.asarray(rng.random((rows_out, deg)), jnp.float32)
    stm = jax.jit(lambda a, b, c, d: halo_spmm_stream_pallas(
        a, b, c, d, chunk_rows=chunk, interpret=True))
    rows = []
    for pct in (5, 25, 75):
        k = max(int(round(n_chunks * pct / 100)), 1)
        nbr = np.empty((rows_out, deg), np.int64)
        for b in range(n_blocks):
            mine = rng.choice(n_chunks, size=k, replace=False)
            base = mine[rng.integers(0, k, (128, deg))] * chunk
            nbr[b * 128:(b + 1) * 128] = base + rng.integers(
                0, chunk, (128, deg))
        nbr = jnp.asarray(np.minimum(nbr, ntab - 2), jnp.int32)
        wl = build_chunk_worklist(np.asarray(nbr), ntab, chunk)
        skp = jax.jit(lambda a, b, c, d, i, n: halo_spmm_skip_pallas(
            a, b, c, d, wl_ids=i, wl_cnt=n, chunk_rows=chunk,
            interpret=True))
        ids, cnt = jnp.asarray(wl.ids), jnp.asarray(wl.cnt)
        np.testing.assert_array_equal(
            np.asarray(skp(nbr, wts, data, scale, ids, cnt)),
            np.asarray(stm(nbr, wts, data, scale)))
        rows.append({
            "name": f"kernel/halo_spmm_stream_dense_occ{pct:02d}",
            "us_per_call": round(time_call(stm, nbr, wts, data, scale), 1),
            "chunks_visited": n_blocks * n_chunks,
            "bytes_streamed": n_blocks * n_chunks * chunk_bytes})
        rows.append({
            "name": f"kernel/halo_spmm_stream_skip_occ{pct:02d}",
            "us_per_call": round(time_call(skp, nbr, wts, data, scale,
                                           ids, cnt), 1),
            "chunks_visited": wl.visited_chunks,
            "bytes_streamed": wl.visited_chunks * chunk_bytes})
    return rows


def _order_sweep() -> list[dict]:
    """Ordered-vs-unordered locality on a REAL graph (not the synthetic
    pinned-occupancy slabs above): the same community power-law graph is
    partitioned with order="none" and order="rcm" and the resulting
    stacked chunk worklists compared — chunks visited, bytes streamed per
    layer (int8 slab convention of the sweep above).  The structural
    claim recorded here: RCM lowers occupancy, so the chunk-skipping
    kernel (``backend="pallas_skip"``) streams fewer bytes than under
    the identity layout.  us_per_call is the host-side partition+ordering
    build time (the cost of the locality pass)."""
    chunk, feat, M = 256, 128, 8
    g = community_powerlaw_graph(num_nodes=40000, seed=0,
                                 name="bench-powerlaw")
    chunk_bytes = chunk * (feat * 1 + 4)
    rows = []
    for order in ("none", "rcm"):
        t0 = time.perf_counter()
        sp = build_partitions(g, M, halo_weight=0.25, order=order,
                              order_chunk_rows=chunk)
        dt = (time.perf_counter() - t0) * 1e6
        wl = sp.chunk_worklist(chunk)
        rows.append({
            "name": f"kernel/halo_spmm_order_{order}",
            "us_per_call": round(dt, 1),
            "occupancy": round(wl.occupancy, 4),
            "chunks_visited": wl.visited_chunks,
            "chunks_total": wl.total_pairs,
            "bytes_streamed": wl.visited_chunks * chunk_bytes})
    return rows


def run() -> list[dict]:
    rng = np.random.default_rng(0)
    rows = []
    # SpMM: aggregation for a 4096-node subgraph, deg 16, d=128.
    nbr = jnp.asarray(rng.integers(0, 4097, (4096, 16)), jnp.int32)
    wts = jnp.asarray(rng.random((4096, 16)), jnp.float32)
    tab = jnp.asarray(rng.normal(size=(4097, 128)), jnp.float32)
    f = jax.jit(lambda a, b, c: spmm(a, b, c, backend="jnp"))
    rows.append({"name": "kernel/spmm_4096x16x128",
                 "us_per_call": round(time_call(f, nbr, wts, tab), 1)})
    # Resident vs streaming fused halo pull+aggregate (interpret mode)
    # over a 2048-row int8 slab — 4 chunks of 512 for the streaming path.
    h_nbr = jnp.asarray(rng.integers(0, 2048, (128, 8)), jnp.int32)
    h_wts = jnp.asarray(rng.random((128, 8)), jnp.float32)
    slab = jnp.asarray(rng.normal(size=(2048, 128)), jnp.float32)
    data, scale = hx.quantize_rows(slab, hx.HaloPrecision("int8"))
    data = data.at[-1].set(0)
    res = jax.jit(lambda a, b, c, d: halo_spmm_pallas(
        a, b, c, d, interpret=True))
    stm = jax.jit(lambda a, b, c, d: halo_spmm_stream_pallas(
        a, b, c, d, chunk_rows=512, interpret=True))
    rows.append({"name": "kernel/halo_spmm_resident_2048x128_int8",
                 "us_per_call": round(time_call(res, h_nbr, h_wts, data,
                                                scale), 1)})
    rows.append({"name": "kernel/halo_spmm_stream_2048x128_int8",
                 "us_per_call": round(time_call(stm, h_nbr, h_wts, data,
                                                scale), 1)})
    # Dense vs chunk-skipping stream across pinned occupancies.
    rows.extend(_occupancy_sweep(rng))
    # Ordered vs unordered layout on a real community power-law graph.
    rows.extend(_order_sweep())
    # Attention 2x1024x8x64.
    q = jnp.asarray(rng.normal(size=(2, 1024, 8, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 1024, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 1024, 2, 64)), jnp.bfloat16)
    g = jax.jit(lambda a, b, c: multi_head_attention(a, b, c,
                                                     backend="jnp"))
    rows.append({"name": "kernel/attn_dense_1k",
                 "us_per_call": round(time_call(g, q, k, v), 1)})
    h = jax.jit(lambda a, b, c: chunked_attention(a, b, c, chunk=256))
    rows.append({"name": "kernel/attn_chunked_1k",
                 "us_per_call": round(time_call(h, q, k, v), 1)})
    return rows


if __name__ == "__main__":
    emit(run())
