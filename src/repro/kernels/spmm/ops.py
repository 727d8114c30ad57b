"""Jitted public entry points for neighbor aggregation.

Backends (``backend=``; anything else raises):

  * ``"auto"`` (default) — the compiled Pallas kernels when
    ``jax.default_backend() == "tpu"``, the jnp reference elsewhere (the
    CPU test suite; interpret-mode Pallas is Python-slow and the oracle is
    the same math).
  * ``"jnp"`` — the reference (:mod:`repro.kernels.spmm.ref`).
  * ``"pallas"`` / ``"pallas_stream"`` / ``"pallas_skip"`` — the compiled
    kernels: the selection ladder below, or a forced streamed variant.
  * the same three names with an ``_interpret`` suffix — the kernel bodies
    run by the Pallas interpreter (CPU tests of the kernels themselves).

``spmm`` (the in-subgraph side) and ``halo_spmm`` (the halo side) share
one kernel ladder, selected by shapes:

  * **resident** — the table's 128-wide feature stripe plus its one-hot
    tile fit the ``resident_max_bytes`` VMEM budget (default
    ``RESIDENT_STRIPE_MAX_BYTES``): one chunk spans the table.
  * **dense stream** — above the budget: chunked double-buffered DMA of
    every ``chunk_rows``-row table chunk past the accumulator tile.
  * **skip stream** — above the budget *and* a (row_block × chunk)
    worklist is supplied whose static measured ``occupancy`` is at or
    below ``skip_occupancy_max`` (default ``SKIP_OCCUPANCY_MAX``): stream
    only the chunks each row block references
    (:func:`repro.kernels.spmm.halo_pull.halo_spmm_skip_pallas`).  At
    high occupancy the worklist degenerates to the dense schedule while
    paying the scalar-prefetch indirection, so the dense stream wins —
    hence the threshold, overridable per call (it is a static, jit-cache-
    keyed argument, like every selection knob here).

The kernels compute the forward product; the backward pass is the VJP of
the jnp reference (a transposed ELL product XLA lowers as gather +
scatter-add), attached through ``jax.custom_vjp``, so every backend
differentiates identically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.spmm.halo_pull import (BLOCK_F, BLOCK_ROWS,
                                          STREAM_CHUNK_ROWS,
                                          halo_spmm_pallas,
                                          halo_spmm_skip_pallas,
                                          halo_spmm_stream_pallas,
                                          resident_rows)
from repro.kernels.spmm.ref import halo_spmm_ref

# VMEM budget of the resident kernel: the table's feature stripe, its
# scale row and the (128 × rows) fp32 one-hot tile with its transpose.
# A 128-wide fp32 table reaches it at ~2.7k rows (int8: ~3.6k rows);
# above it, the table streams through chunked double-buffered DMA.
RESIDENT_STRIPE_MAX_BYTES = 4 * 1024 * 1024

# Highest (row_block × chunk) occupancy at which the chunk-skipping
# stream is auto-selected over the dense stream.  Above it most chunks
# are visited anyway and the dense schedule's simpler (non-indirected)
# prefetch wins; below it DMA bytes shrink proportionally to occupancy.
SKIP_OCCUPANCY_MAX = 0.5

_KERNELS = ("pallas", "pallas_stream", "pallas_skip")
BACKENDS = ("auto", "jnp") + _KERNELS + tuple(f"{k}_interpret"
                                              for k in _KERNELS)


def resolve_backend(backend: str) -> str:
    """Validate ``backend`` and resolve ``"auto"`` for this process."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown aggregation backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


def vma_checkable(backend: str) -> bool:
    """Whether a ``shard_map`` running this backend's aggregation can keep
    its varying-axes type check (``check_vma``).  The compiled kernels
    type their outputs; the Pallas interpreter's loops carry no
    varying-axes types, so JAX rejects them under the check."""
    return not resolve_backend(backend).endswith("_interpret")


def select_kernel(data_shape, data_dtype, *, has_scale: bool = False,
                  pred_dtype=None, has_pscale: bool = False,
                  has_worklist: bool = False, occupancy: float = None,
                  resident_max_bytes: int = None,
                  skip_occupancy_max: float = None) -> str:
    """The ladder rung (``"resident"``, ``"stream"`` or ``"skip"``) a
    table of this shape selects — a pure function of static facts, so the
    launchers can report which kernel each aggregation call runs."""
    if resident_max_bytes is None:
        resident_max_bytes = RESIDENT_STRIPE_MAX_BYTES
    rows = resident_rows(data_shape[0])
    bf = min(BLOCK_F, data_shape[1])
    stripe = rows * (bf * jnp.dtype(data_dtype).itemsize
                     + (4 if has_scale else 0) + 2 * BLOCK_ROWS * 4)
    if pred_dtype is not None:
        # The history table rides the same tiles.
        stripe += rows * (bf * jnp.dtype(pred_dtype).itemsize
                          + (4 if has_pscale else 0))
    if stripe <= resident_max_bytes:
        return "resident"
    if skip_occupancy_max is None:
        skip_occupancy_max = SKIP_OCCUPANCY_MAX
    if (has_worklist and occupancy is not None
            and occupancy <= skip_occupancy_max):
        return "skip"
    return "stream"


def _pad_dim(x: jax.Array, axis: int, multiple: int,
             value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _kernel_forward(kind: str, chunk_rows: int, gamma: float,
                    interpret: bool, nbr, wts, data, scale, wl_ids, wl_cnt,
                    pdata, pscale) -> jax.Array:
    """Pad to the kernels' block sizes, run the ``kind`` kernel, unpad."""
    rows, feat = nbr.shape[0], data.shape[1]
    nbr_p = _pad_dim(nbr, 0, BLOCK_ROWS, value=data.shape[0] - 1)
    wts_p = _pad_dim(wts, 0, BLOCK_ROWS, value=0)
    dat_p = _pad_dim(data, 1, BLOCK_F, value=0)
    pdat_p = _pad_dim(pdata, 1, BLOCK_F) if pdata is not None else None
    common = dict(pdata=pdat_p, pscale=pscale, gamma=gamma,
                  interpret=interpret)
    if kind == "skip":
        out = halo_spmm_skip_pallas(nbr_p, wts_p, dat_p, scale,
                                    wl_ids=wl_ids, wl_cnt=wl_cnt,
                                    chunk_rows=chunk_rows, **common)
    elif kind == "stream":
        out = halo_spmm_stream_pallas(nbr_p, wts_p, dat_p, scale,
                                      chunk_rows=chunk_rows, **common)
    else:
        out = halo_spmm_pallas(nbr_p, wts_p, dat_p, scale, **common)
    return out[:rows, :feat]


_kernel_agg = jax.custom_vjp(_kernel_forward, nondiff_argnums=(0, 1, 2, 3))


def _kernel_agg_fwd(kind, chunk_rows, gamma, interpret, *args):
    return _kernel_forward(kind, chunk_rows, gamma, interpret, *args), args


def _kernel_agg_bwd(kind, chunk_rows, gamma, interpret, args, g):
    nbr, wts, data, scale, wl_ids, wl_cnt, pdata, pscale = args
    floats = {name: x for name, x in (("wts", wts), ("data", data),
                                      ("scale", scale), ("pdata", pdata),
                                      ("pscale", pscale))
              if x is not None and jnp.issubdtype(x.dtype, jnp.floating)}
    given = dict(wts=wts, data=data, scale=scale, pdata=pdata,
                 pscale=pscale)

    def ref(fl):
        kw = {**given, **fl}
        return halo_spmm_ref(nbr, kw["wts"], kw["data"], kw["scale"],
                             kw["pdata"], kw["pscale"], gamma)

    _, vjp = jax.vjp(ref, floats)
    (ct,) = vjp(g)
    return (None, ct.get("wts"), ct.get("data"), ct.get("scale"), None,
            None, ct.get("pdata"), ct.get("pscale"))


_kernel_agg.defvjp(_kernel_agg_fwd, _kernel_agg_bwd)


@functools.partial(jax.jit, static_argnames=("backend",))
def spmm(nbr: jax.Array, wts: jax.Array, table: jax.Array,
         backend: str = "auto") -> jax.Array:
    """Neighbor aggregation out[i] = Σ_k wts[i,k]·table[nbr[i,k]] — the
    in-subgraph side; the same kernel ladder as :func:`halo_spmm`.

    Handles arbitrary (unpadded) shapes by padding to kernel block sizes.
    """
    return halo_spmm(nbr, wts, table, backend=backend)


@jax.jit
def halo_gather(nbr: jax.Array, data: jax.Array,
                scale: jax.Array = None) -> jax.Array:
    """Gather + dequantize individual slab rows: out[..., :] =
    dequant(data[nbr[...]]).

    The non-reducing read primitive of the serving query path: GAT's
    attention needs every neighbor row individually (scores before the
    weighted sum), and the hot-row cache's miss fill wants raw rows —
    neither can ride :func:`halo_spmm`, whose contraction is fused.
    gcn/sage reductions should keep using :func:`halo_spmm` so they hit
    the resident/stream/skip selection ladder.
    """
    rows = jnp.take(data, nbr, axis=0).astype(jnp.float32)
    if scale is not None:
        rows = rows * jnp.take(scale, nbr, axis=0)
    return rows


@functools.partial(jax.jit,
                   static_argnames=("backend", "resident_max_bytes",
                                    "chunk_rows", "occupancy",
                                    "skip_occupancy_max", "gamma"))
def halo_spmm(nbr: jax.Array, wts: jax.Array, data: jax.Array,
              scale: jax.Array = None, wl_ids: jax.Array = None,
              wl_cnt: jax.Array = None, pdata: jax.Array = None,
              pscale: jax.Array = None, gamma: float = 1.0,
              backend: str = "auto",
              resident_max_bytes: int = None, chunk_rows: int = None,
              occupancy: float = None,
              skip_occupancy_max: float = None) -> jax.Array:
    """Fused halo pull+aggregate against the compact HaloExchange slab.

    out[i] = Σ_k wts[i,k] · dequant(data[nbr[i,k]]) with optional per-row
    int8 scales — the out-of-subgraph side of Eq. 5 read directly from
    storage precision (no materialized per-subgraph halo table).

    With a predictor slab (``pdata``/``pscale``, the SAT history rows in
    the data slab's exact layout; see ``repro.core.predictor``) every
    gathered row becomes the staleness-alleviated prediction
    ``dequant(data[s]) + gamma·dequant(pdata[s])`` — fused into the
    kernel the ladder selects, a second product over the same one-hot
    tile rather than a second aggregation pass.  ``gamma`` is static
    (jit-cache-keyed); with ``pdata=None`` the emitted program is exactly
    the predictor-free one.

    Optional occupancy-aware streaming (see module docstring for the
    selection ladder):

      wl_ids / wl_cnt: the (row_blocks, max_chunks)/(row_blocks,) chunk
        worklist from ``repro.graph.partition.build_chunk_worklist`` —
        built with the same ``chunk_rows`` and 128-row blocks.
      occupancy: the worklist's static measured occupancy
        (``ChunkWorklist.occupancy``), used for auto-selection; it is a
        host-side float (jit-cache key), never a traced value.
      chunk_rows / resident_max_bytes / skip_occupancy_max: overrides of
        the module-level streaming constants; all static (jit-cache-
        keyed), so an explicit override never aliases executables traced
        with the defaults.
    """
    backend = resolve_backend(backend)
    if backend == "jnp":
        return halo_spmm_ref(nbr, wts, data, scale, pdata, pscale, gamma)

    interpret = backend.endswith("_interpret")
    kernel = backend.removesuffix("_interpret")
    has_worklist = wl_ids is not None and wl_cnt is not None
    if kernel == "pallas_skip" and not has_worklist:
        raise ValueError(f"backend={backend!r} needs the (wl_ids, wl_cnt)"
                         " chunk worklist")
    if kernel == "pallas":
        kind = select_kernel(
            data.shape, data.dtype, has_scale=scale is not None,
            pred_dtype=pdata.dtype if pdata is not None else None,
            has_pscale=pscale is not None, has_worklist=has_worklist,
            occupancy=occupancy, resident_max_bytes=resident_max_bytes,
            skip_occupancy_max=skip_occupancy_max)
    else:
        kind = kernel.removeprefix("pallas_")
    if kind != "skip":
        wl_ids = wl_cnt = None
    if chunk_rows is None:
        chunk_rows = STREAM_CHUNK_ROWS
    return _kernel_agg(kind, chunk_rows, gamma, interpret, nbr, wts, data,
                       scale, wl_ids, wl_cnt, pdata, pscale)
