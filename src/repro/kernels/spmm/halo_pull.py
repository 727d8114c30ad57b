"""Pallas TPU kernels: ELL neighbor aggregation over a (quantized) table.

Computes the aggregation side of DIGEST's Eq. 5

    out[i] = sum_k wts[i, k] * dequant(table[nbr[i, k]])

for both sides of the split: the in-subgraph product against the local
``(S+1, d)`` table, and the out-of-subgraph product against the
HaloExchange compact slab — fp32, bf16, or int8 with per-row fp32 scales
— whose ``nbr`` holds slot indices (sentinel == table.shape[0]-1, a zero
row).  Fusing the gather into the product means the non-pull epochs of
Algorithm 1 never materialize a ``(M, L-1, H, hidden)`` halo cache, and
int8 rows are dequantized in VMEM.

**Inner loop.**  Mosaic lowers no row gather (``jnp.take`` of a vector of
row ids from a VMEM table), so each (128-row block × table chunk) pair is
computed as a dense product on the MXU:

    A[r, c] = sum_k wts[r, k] · [nbr[r, k] == base + c]      (VPU, iota ==)
    out[r] += (A · scale[base:base+C]) @ chunk                 (MXU, fp32)

The ELL arrays enter transposed, ``(deg, rows)``, so the degree loop reads
one sublane row per step (a dynamic *sublane* slice, which Mosaic lowers;
a dynamic lane slice it does not) and builds Aᵀ with a sublane iota; one
transpose per chunk turns it into A.  The int8 dequant scale rides as a
lane-dense ``(1, rows)`` row folded into A's columns, so no ``(n, 1)``
column pads to 128 lanes in VMEM.  The SAT predictor epilogue (``pdata``
/ ``pscale`` / ``gamma``, see ``repro.core.predictor``) is a second
product ``(A · γ·pscale) @ pchunk`` over the same A — never a second
aggregation pass; ``gamma`` is a static (jit-cache-keyed) float, and with
``pdata=None`` the emitted kernel is exactly the predictor-free one.

The A tile costs ``deg × 128 × C`` VPU compares per (block, chunk) pair,
so work grows with rows × table rows; the chunk-skipping grid below is
what keeps it proportional to the pairs the graph actually occupies.

**Grids.**  One body (:func:`_accumulate`), two grids:

  * **Chunked** (:func:`halo_spmm_stream_pallas`): grid = (row_blocks,
    feature_blocks, table_chunks).  The chunk axis is innermost and the
    output block index is chunk-invariant, so the accumulator tile stays
    in VMEM while the Pallas pipeline double-buffers the HBM→VMEM DMA of
    chunk c+1 behind the product of chunk c.  VMEM residency is O(chunk).
    The **resident** kernel (:func:`halo_spmm_pallas`) is this grid with
    one chunk spanning the whole (128-row padded) table: its block index
    never changes, so the table is fetched once.
  * **Chunk-skipping** (:func:`halo_spmm_skip_pallas`): grid = (row_blocks,
    feature_blocks, ``max_chunks_per_block``); the table BlockSpec's index
    map reads ``wl_ids[i, t]`` from a scalar-prefetch worklist
    (:class:`repro.graph.partition.ChunkWorklist`), so row block i streams
    only the chunks its edges reference.  Padded worklist entries repeat
    the last visited chunk (re-addressed, no DMA) and are masked out
    (``t >= cnt``), so the result is **bitwise identical** to the chunked
    grid at the same ``chunk_rows``: a skipped chunk only ever contributed
    an all-zero A tile.

Every entry point takes ``interpret`` as a required keyword: the kernels
run compiled on a TPU, and interpret mode (CPU, for tests) is asked for
by name, never reached by default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Row/feature tile of every aggregation kernel: MXU/VPU-aligned.
BLOCK_ROWS = 128
BLOCK_F = 128

# Streaming tile height: a 512 × 128 fp32 chunk is 256 KiB per buffer (×2
# for the double buffer), and its 128 × 512 fp32 A tile another 256 KiB —
# far under the VMEM budget while long enough to amortize DMA issue.
STREAM_CHUNK_ROWS = 512


def _accumulate(base, nbr_ref, wts_ref, data_ref, scale_ref, pdata_ref,
                pscale_ref, gamma: float, out_ref):
    """Add one table chunk's contribution to the output tile.

    ``nbr_ref``/``wts_ref`` are the block's transposed ELL ``(deg, BR)``;
    ``data_ref`` the ``(C, BF)`` chunk holding table rows
    ``[base, base + C)``; ``scale_ref`` its ``(1, C)`` scales.  Edges whose
    slot falls outside the chunk add exact zeros to A.  The single body
    every grid runs, so their bitwise-equality invariants have one source
    of truth."""
    deg, br = nbr_ref.shape
    chunk = data_ref.shape[0]
    slot = jax.lax.broadcasted_iota(jnp.int32, (chunk, br), 0) + base

    def body(k, a_t):
        hit = slot == nbr_ref[pl.ds(k, 1), :]
        w = wts_ref[pl.ds(k, 1), :].astype(jnp.float32)
        return a_t + jnp.where(hit, w, 0.0)

    a = jax.lax.fori_loop(0, deg, body,
                          jnp.zeros((chunk, br), jnp.float32)).T

    def product(a_scaled, tab):
        return jnp.dot(a_scaled, tab.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    acc = product(a * scale_ref[...], data_ref[...])
    if pdata_ref is not None:
        acc = acc + product(a * (jnp.float32(gamma) * pscale_ref[...]),
                            pdata_ref[...])
    out_ref[...] += acc


def _out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A kernel output's type.  Inside a ``shard_map`` it varies over every
    mesh axis any operand varies over (the ``check_vma`` type check)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands
                              if x is not None))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _prepare(nbr, wts, data, scale, pdata, pscale, chunk_rows: int,
             interpret: bool):
    """Shared operand layout of every grid: transposed ELL, lane-dense
    scale rows, table (and scales) padded to a whole number of chunks
    (zero rows no index reaches)."""
    rows, deg = nbr.shape
    n_tab, feat = data.shape
    br = min(BLOCK_ROWS, rows)
    bf = min(BLOCK_F, feat)
    if rows % br or feat % bf:
        raise ValueError(f"rows={rows} feat={feat} must be divisible by "
                         f"block ({br},{bf}); pad upstream")
    if not interpret and chunk_rows % 128:
        raise ValueError(f"chunk_rows={chunk_rows} must be a multiple of "
                         f"128 for the compiled kernel (lane tile)")

    def scale_row(s):
        if s is None:
            return jnp.ones((1, n_tab), jnp.float32)
        return s.reshape(1, n_tab).astype(jnp.float32)

    pad = (-n_tab) % chunk_rows
    tables = [data, scale_row(scale)]
    if pdata is not None:
        tables += [pdata, scale_row(pscale)]
    tables = [jnp.pad(t, ((0, pad), (0, 0)) if i % 2 == 0
                      else ((0, 0), (0, pad)))
              for i, t in enumerate(tables)]
    return (nbr.T, wts.T, tables, br, bf, (n_tab + pad) // chunk_rows)


def _table_specs(chunk_rows: int, bf: int, pred: bool, chunk_of):
    """BlockSpecs of (data, scale[, pdata, pscale]); ``chunk_of`` maps the
    grid indices (and any scalar-prefetch refs) to the chunk id."""
    def data_spec():
        return pl.BlockSpec((chunk_rows, bf),
                            lambda i, j, t, *p: (chunk_of(i, t, *p), j))

    def scale_spec():
        return pl.BlockSpec((1, chunk_rows),
                            lambda i, j, t, *p: (0, chunk_of(i, t, *p)))
    specs = [data_spec(), scale_spec()]
    if pred:
        specs += [data_spec(), scale_spec()]
    return specs


def _chunked_call(nbr, wts, data, scale, pdata, pscale, gamma: float,
                  chunk_rows: int, interpret: bool) -> jax.Array:
    rows = nbr.shape[0]
    feat = data.shape[1]
    pred = pdata is not None
    nbr_t, wts_t, tables, br, bf, n_chunks = _prepare(
        nbr, wts, data, scale, pdata, pscale, chunk_rows, interpret)
    deg = nbr.shape[1]

    def kernel(nbr_ref, wts_ref, data_ref, scale_ref, *rest):
        pdata_ref, pscale_ref = rest[:2] if pred else (None, None)
        out_ref = rest[-1]
        c = pl.program_id(2)

        @pl.when(c == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        _accumulate(c * chunk_rows, nbr_ref, wts_ref, data_ref, scale_ref,
                    pdata_ref, pscale_ref, gamma, out_ref)

    ell_spec = pl.BlockSpec((deg, br), lambda i, j, c: (0, i))
    return pl.pallas_call(
        kernel,
        # Chunk axis innermost: the output block index is chunk-invariant,
        # so the accumulator tile stays in VMEM while chunks stream past.
        grid=(rows // br, feat // bf, n_chunks),
        in_specs=[ell_spec, ell_spec] + _table_specs(
            chunk_rows, bf, pred, lambda i, c: c),
        out_specs=pl.BlockSpec((br, bf), lambda i, j, c: (i, j)),
        out_shape=_out_struct((rows, feat), jnp.float32, nbr_t, wts_t,
                              *tables),
        interpret=interpret,
    )(nbr_t, wts_t, *tables)


def resident_rows(n_tab: int) -> int:
    """Table rows the resident kernel carries: ``n_tab`` padded to a whole
    128-row tile (the A tile's lane dimension)."""
    return -(-n_tab // 128) * 128


@functools.partial(jax.jit, static_argnames=("gamma", "interpret"))
def halo_spmm_pallas(nbr: jax.Array, wts: jax.Array, data: jax.Array,
                     scale: jax.Array = None, pdata: jax.Array = None,
                     pscale: jax.Array = None, gamma: float = 1.0, *,
                     interpret: bool) -> jax.Array:
    """Resident aggregation: the whole table is one chunk in VMEM.

    Args:
      nbr:   (rows, deg) int32 — table row ids (< data.shape[0]).
      wts:   (rows, deg) float — 0 at padding slots.
      data:  (n_tab, feat) table incl. sentinel row (fp32/bf16/int8).
      scale: optional (n_tab, 1) fp32 per-row dequant scales.
      pdata/pscale: optional predictor-history table in the same layout;
        rows read as dequant(data) + gamma·dequant(pdata).
      gamma: static extrapolation coefficient (jit-cache-keyed).
      interpret: run the kernel body in the Pallas interpreter (CPU).
    Returns:
      (rows, feat) float32 result.
    """
    return _chunked_call(nbr, wts, data, scale, pdata, pscale, gamma,
                         resident_rows(data.shape[0]), interpret)


@functools.partial(jax.jit,
                   static_argnames=("chunk_rows", "gamma", "interpret"))
def halo_spmm_stream_pallas(nbr: jax.Array, wts: jax.Array,
                            data: jax.Array, scale: jax.Array = None,
                            pdata: jax.Array = None,
                            pscale: jax.Array = None, gamma: float = 1.0,
                            chunk_rows: int = STREAM_CHUNK_ROWS, *,
                            interpret: bool) -> jax.Array:
    """Streaming aggregation: the table never resides in VMEM whole.

    Same contract as :func:`halo_spmm_pallas`, but the table is tiled into
    ``chunk_rows``-row chunks streamed through VMEM by the Pallas pipeline
    (double-buffered HBM→VMEM DMA on TPU) while the output tile
    accumulates in place.  fp32 accumulation is reassociated across
    chunks, so results match the resident kernel within dtype tolerance
    (bitwise when one chunk spans the table).
    """
    return _chunked_call(nbr, wts, data, scale, pdata, pscale, gamma,
                         chunk_rows, interpret)


@functools.partial(jax.jit, static_argnames=("chunk_rows", "gamma",
                                             "interpret", "count_visits"))
def halo_spmm_skip_pallas(nbr: jax.Array, wts: jax.Array, data: jax.Array,
                          scale: jax.Array = None,
                          wl_ids: jax.Array = None,
                          wl_cnt: jax.Array = None,
                          pdata: jax.Array = None,
                          pscale: jax.Array = None, gamma: float = 1.0,
                          chunk_rows: int = STREAM_CHUNK_ROWS, *,
                          interpret: bool,
                          count_visits: bool = False):
    """Chunk-skipping streamed aggregation: occupancy-proportional work.

    Same contract as :func:`halo_spmm_stream_pallas`, plus a precomputed
    worklist (``repro.graph.partition.build_chunk_worklist`` with the
    same ``chunk_rows`` and the kernel's 128-row blocks):

      wl_ids: (row_blocks, max_chunks) int32 — ascending chunk ids each
        row block must visit, padded by repeating the last entry.
      wl_cnt: (row_blocks,) int32 — valid prefix length per block.

    The innermost grid dimension runs over the *worklist position* t, and
    the table BlockSpec resolves it to chunk ``wl_ids[i, t]`` via scalar
    prefetch — so the pipeline DMAs, and the VPU builds A tiles for,
    exactly the occupied chunks.  Bitwise equal to the dense stream at the
    same ``chunk_rows``.

    With ``count_visits=True`` a second output (row_blocks, max_chunks)
    int32 records the chunk id processed at each (block, t) — ``-1`` at
    masked padding steps — so tests can assert visited chunks ==
    worklist entries.  Interpret mode only: the (1, 1) block shape is not
    a legal TPU tile.
    """
    rows, deg = nbr.shape
    n_tab, feat = data.shape
    br = min(BLOCK_ROWS, rows)
    if wl_ids is None or wl_cnt is None:
        raise ValueError("halo_spmm_skip_pallas needs the (wl_ids, wl_cnt)"
                         " worklist; build it with "
                         "repro.graph.partition.build_chunk_worklist")
    n_blocks, max_chunks = wl_ids.shape
    n_chunks = max(-(-n_tab // chunk_rows), 1)
    if n_blocks != rows // br or wl_cnt.shape != (n_blocks,):
        raise ValueError(
            f"worklist geometry mismatch: wl_ids {wl_ids.shape} / wl_cnt "
            f"{wl_cnt.shape} vs {rows // br} row blocks of {br} rows — "
            f"rebuild the worklist with block_rows={br}")
    if max_chunks > n_chunks:
        # A well-formed worklist never lists more distinct chunks than
        # the table tiling has — a wider one means it was built with a
        # smaller chunk_rows than this call's.  (The converse mismatch —
        # a coarser worklist — is undetectable from the traced arrays;
        # keep the build chunk_rows and the call chunk_rows wired to the
        # same knob, as GNNConfig.stream_chunk_rows does.)
        raise ValueError(
            f"worklist chunk-geometry mismatch: wl_ids lists up to "
            f"{max_chunks} chunks per block but a {n_tab}-row slab at "
            f"chunk_rows={chunk_rows} has only {n_chunks} — rebuild the "
            f"worklist with this chunk_rows")
    pred = pdata is not None
    nbr_t, wts_t, tables, br, bf, _ = _prepare(
        nbr, wts, data, scale, pdata, pscale, chunk_rows, interpret)

    def kernel(ids_ref, cnt_ref, nbr_ref, wts_ref, data_ref, scale_ref,
               *rest):
        pdata_ref, pscale_ref = rest[:2] if pred else (None, None)
        out_refs = rest[2:] if pred else rest
        out_ref = out_refs[0]
        i = pl.program_id(0)
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        # Worklist lookup: this grid step carries table chunk ids[i, t]
        # (the table BlockSpecs used the same entry, so that chunk's tile
        # is what sits in VMEM).  Entries at t >= cnt[i] repeat the
        # previous chunk — already resident, no DMA — and are masked out.
        active = t < cnt_ref[i]

        @pl.when(active)
        def _acc():
            _accumulate(ids_ref[i, t] * chunk_rows, nbr_ref, wts_ref,
                        data_ref, scale_ref, pdata_ref, pscale_ref, gamma,
                        out_ref)

        if count_visits:
            visit_ref = out_refs[1]

            @pl.when(pl.program_id(1) == 0)
            def _log():
                visit_ref[0, 0] = jnp.where(active, ids_ref[i, t],
                                            jnp.int32(-1))

    operands = (wl_ids, wl_cnt, nbr_t, wts_t, *tables)
    out_shape = [_out_struct((rows, feat), jnp.float32, *operands)]
    out_specs = [pl.BlockSpec((br, bf), lambda i, j, t, ids, cnt: (i, j))]
    if count_visits:
        out_shape.append(_out_struct((n_blocks, max_chunks), jnp.int32,
                                     *operands))
        out_specs.append(pl.BlockSpec((1, 1),
                                      lambda i, j, t, ids, cnt: (i, t)))
    ell_spec = pl.BlockSpec((deg, br), lambda i, j, t, ids, cnt: (0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # Worklist position innermost: the output block index is
        # t-invariant (accumulator stays in VMEM) and the table BlockSpec
        # resolves t through the prefetched worklist, so the pipeline
        # prefetches chunk ids[i, t+1] during chunk ids[i, t].
        grid=(rows // br, feat // bf, max_chunks),
        in_specs=[ell_spec, ell_spec] + _table_specs(
            chunk_rows, bf, pred, lambda i, t, ids, cnt: ids[i, t]),
        out_specs=out_specs if count_visits else out_specs[0],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape if count_visits else out_shape[0],
        interpret=interpret,
    )(*operands)
