"""Pallas ELL SpMM kernel vs pure-jnp oracle: shape/dtype sweeps, plus the
fused HaloExchange pull+aggregate variant (precision-aware slab gather)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import halo_exchange as hx
from repro.kernels.spmm import (BACKENDS, RESIDENT_STRIPE_MAX_BYTES,
                                halo_spmm, halo_spmm_gather, halo_spmm_ref,
                                halo_spmm_stream_pallas, resolve_backend,
                                select_kernel, spmm, spmm_ref)


def _case(rng, rows, deg, ncols, feat, dtype):
    nbr = rng.integers(0, ncols + 1, size=(rows, deg)).astype(np.int32)
    wts = (rng.random((rows, deg)) * (nbr < ncols)).astype(np.float32)
    table = rng.normal(size=(ncols + 1, feat)).astype(dtype)
    table[-1] = 0
    return jnp.asarray(nbr), jnp.asarray(wts), jnp.asarray(table)


@pytest.mark.parametrize("rows,deg,ncols,feat", [
    (128, 4, 64, 128), (256, 16, 300, 128), (128, 1, 5, 256),
    (384, 9, 57, 70), (17, 3, 9, 33),
])
def test_spmm_matches_ref(rows, deg, ncols, feat):
    rng = np.random.default_rng(rows + deg)
    nbr, wts, table = _case(rng, rows, deg, ncols, feat, np.float32)
    out = spmm(nbr, wts, table, backend="pallas_interpret")
    ref = spmm_ref(nbr, wts, table)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_spmm_dtypes(dtype):
    rng = np.random.default_rng(7)
    nbr, wts, table = _case(rng, 128, 8, 100, 128, np.float32)
    table = table.astype(dtype)
    out = spmm(nbr, wts, table, backend="pallas_interpret")
    ref = spmm_ref(nbr, wts, table)
    np.testing.assert_allclose(out, np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@settings(max_examples=25, deadline=None)
@given(rows=st.integers(1, 200), deg=st.integers(1, 12),
       ncols=st.integers(1, 150), feat=st.integers(1, 160),
       seed=st.integers(0, 2**31 - 1))
def test_spmm_property(rows, deg, ncols, feat, seed):
    rng = np.random.default_rng(seed)
    nbr, wts, table = _case(rng, rows, deg, ncols, feat, np.float32)
    out = spmm(nbr, wts, table, backend="pallas_interpret")
    ref = spmm_ref(nbr, wts, table)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("storage", ["fp32", "bf16", "int8"])
def test_halo_spmm_fused_dequant(storage):
    """Fused pull+aggregate == dequantize-then-spmm, at every precision."""
    rng = np.random.default_rng(11)
    nbr, wts, table = _case(rng, 64, 6, 50, 48, np.float32)
    data, scale = hx.quantize_rows(table, hx.HaloPrecision(storage))
    # the sentinel row stays representable as exact zero
    data = data.at[-1].set(0)
    deq = hx.dequantize_rows(data, scale)
    want = spmm_ref(nbr, wts, deq)
    got_ref = halo_spmm_ref(nbr, wts, data, scale)
    got_pl = halo_spmm(nbr, wts, data, scale, backend="pallas_interpret")
    np.testing.assert_allclose(got_ref, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got_pl, want, atol=1e-5, rtol=1e-5)


def test_halo_spmm_fp32_equals_spmm():
    """With an fp32 slab and no scales the fused kernel IS plain spmm."""
    rng = np.random.default_rng(13)
    nbr, wts, table = _case(rng, 128, 8, 100, 64, np.float32)
    np.testing.assert_array_equal(
        np.asarray(halo_spmm(nbr, wts, table, None, backend="jnp")),
        np.asarray(spmm(nbr, wts, table, backend="jnp")))


@pytest.mark.parametrize("storage", ["fp32", "bf16", "int8"])
def test_halo_spmm_streaming_matches_resident(storage):
    """The chunked double-buffered variant == the resident kernel within
    dtype tolerance, on a slab spanning several chunks (incl. a ragged
    final chunk)."""
    rng = np.random.default_rng(17)
    ncols, feat, chunk = 300, 64, 128       # 3 chunks: 128+128+45
    nbr, wts, table = _case(rng, 128, 6, ncols, feat, np.float32)
    data, scale = hx.quantize_rows(table, hx.HaloPrecision(storage))
    data = data.at[-1].set(0)
    want = halo_spmm(nbr, wts, data, scale, backend="pallas_interpret")
    got = halo_spmm_stream_pallas(nbr, wts, data, scale,
                                  chunk_rows=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_halo_spmm_stream_single_chunk_exact():
    """One chunk covering the whole slab: no reassociation — bitwise equal
    to the resident scaled kernel."""
    rng = np.random.default_rng(19)
    nbr, wts, table = _case(rng, 128, 4, 60, 128, np.float32)
    data, scale = hx.quantize_rows(table, hx.HaloPrecision("int8"))
    data = data.at[-1].set(0)
    want = halo_spmm(nbr, wts, data, scale, backend="pallas_interpret")
    got = halo_spmm_stream_pallas(nbr, wts, data, scale, chunk_rows=64,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_halo_spmm_auto_streams_above_threshold():
    """ops.halo_spmm leaves the resident kernel for the row gather once
    the slab stripe outgrows the VMEM-resident budget.  The threshold is
    passed as a static argument (part of the jit cache key), so the
    shrunken value genuinely retraces — a monkeypatched module global
    would be invisible to an already-cached executable."""
    rng = np.random.default_rng(23)
    nbr, wts, table = _case(rng, 128, 5, 900, 64, np.float32)
    data, scale = hx.quantize_rows(table, hx.HaloPrecision("int8"))
    data = data.at[-1].set(0)
    want = halo_spmm_ref(nbr, wts, data, scale)
    # stripe = 901 rows · (64 B + 4 B scale) ≈ 61 KiB > 1 KiB → gathers
    got = halo_spmm(nbr, wts, data, scale, backend="pallas_interpret",
                    resident_max_bytes=1024)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    # the ladder's result == the gather rung called directly, bitwise
    got_direct = jax.jit(halo_spmm_gather)(nbr, wts, data, scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got_direct))


@pytest.mark.parametrize("storage", ["fp32", "bf16", "int8"])
@pytest.mark.parametrize("pred", [False, True], ids=["plain", "pdata"])
def test_gather_rung_matches_reference(storage, pred):
    """Past the resident budget the ladder runs the row gather: equal to
    the reference and to the one-hot stream kernel within float32
    tolerance, at every storage precision, with and without the SAT
    predictor slab; its gradients (the reference VJP) equal the
    reference's."""
    rng = np.random.default_rng(29)
    nbr, wts, table = _case(rng, 200, 7, 900, 64, np.float32)
    data, scale = hx.quantize_rows(table, hx.HaloPrecision(storage))
    data = data.at[-1].set(0)
    kw = {}
    if pred:
        pdata, pscale = hx.quantize_rows(table[::-1] * 0.5,
                                         hx.HaloPrecision(storage))
        kw = dict(pdata=pdata.at[-1].set(0), pscale=pscale, gamma=0.5)
    # The 901-row table passes a 1 KiB budget: the ladder gathers.
    assert select_kernel(data.shape, data.dtype,
                         has_scale=scale is not None,
                         pred_dtype=kw["pdata"].dtype if pred else None,
                         has_pscale=pred and scale is not None,
                         resident_max_bytes=1024) == "gather"

    def ladder(w, d):
        return halo_spmm(nbr, w, d, scale, backend="pallas",
                         resident_max_bytes=1024, **kw)

    def ref(w, d):
        return halo_spmm_ref(nbr, w, d, scale, **kw)

    got = jax.jit(ladder)(wts, data)
    stream = halo_spmm(nbr, wts, data, scale,
                       backend="pallas_stream_interpret", chunk_rows=256,
                       **kw)
    np.testing.assert_allclose(got, ref(wts, data), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got, stream, atol=1e-5, rtol=1e-5)

    cot = jnp.asarray(rng.normal(size=got.shape), jnp.float32)
    argnums = (0, 1) if jnp.issubdtype(data.dtype, jnp.floating) else (0,)
    grads = jax.grad(lambda w, d: jnp.sum(ladder(w, d) * cot),
                     argnums=argnums)(wts, data)
    want = jax.grad(lambda w, d: jnp.sum(ref(w, d) * cot),
                    argnums=argnums)(wts, data)
    for g, r in zip(grads, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("n_tab,dtype,rung", [
    (2048, jnp.float32, "resident"), (2560, jnp.float32, "resident"),
    (3072, jnp.float32, "gather"), (3584, jnp.int8, "resident"),
    (15753, jnp.float32, "gather"), (104249, jnp.float32, "gather"),
    (104249, jnp.int8, "gather")])
def test_select_kernel_by_table_size(n_tab, dtype, rung):
    """The ladder keeps the resident kernel while the 128-wide stripe
    fits ``RESIDENT_STRIPE_MAX_BYTES`` (about 2.7k fp32 rows, 3.6k int8
    rows) and gathers past it."""
    assert select_kernel((n_tab, 128), dtype,
                         has_scale=dtype == jnp.int8) == rung
    # A budget override moves the threshold: half of it holds none.
    assert select_kernel((n_tab, 128), dtype,
                         resident_max_bytes=RESIDENT_STRIPE_MAX_BYTES // 2
                         ) == "gather"


def test_spmm_dense_oracle():
    """ELL result == dense P @ H for a real partition matrix."""
    from repro.graph import make_dataset, build_partitions
    g = make_dataset("flickr-sim", scale=0.1)
    sp = build_partitions(g, 2)
    m = 0
    x = np.random.default_rng(0).normal(
        size=(sp.part_size + 1, 64)).astype(np.float32)
    x[-1] = 0
    out = spmm(jnp.asarray(sp.in_nbr[m]), jnp.asarray(sp.in_wts[m]),
               jnp.asarray(x), backend="pallas_interpret")
    # dense reconstruction
    S = sp.part_size
    P = np.zeros((S, S + 1))
    for i in range(S):
        for kk in range(sp.in_nbr.shape[-1]):
            P[i, sp.in_nbr[m, i, kk]] += sp.in_wts[m, i, kk]
    np.testing.assert_allclose(out, P @ x, atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_names_resolve(backend):
    """"auto" is the compiled kernel only on a TPU (the jnp reference on
    this CPU); every other documented name is taken as given."""
    want = "jnp" if backend == "auto" else backend
    assert resolve_backend(backend) == want


@pytest.mark.parametrize("backend", ["interpret", "pallas-interpret",
                                     "Pallas", ""])
def test_unknown_backend_raises(backend):
    with pytest.raises(ValueError, match="unknown aggregation backend"):
        resolve_backend(backend)
    nbr, wts, table = _case(np.random.default_rng(0), 8, 2, 5, 4,
                            np.float32)
    with pytest.raises(ValueError, match="unknown aggregation backend"):
        spmm(nbr, wts, table, backend=backend)
