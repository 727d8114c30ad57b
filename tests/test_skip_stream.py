"""Occupancy-aware chunk-skipping halo_spmm: worklist + kernel + wiring.

Covers the PR-4 perf surfaces end to end:

  * the (row_block × chunk) worklist builder (coverage-exactness via the
    masked oracle, sentinel exclusion, padding-by-repeat, geometry guard);
  * ``halo_spmm_skip_pallas`` — **bitwise** equal to the dense stream at
    every storage precision (skipped chunks contribute exact ±0.0 terms),
    tolerance-equal to the resident kernel / jnp oracle, and an
    interpret-mode visit log proving visited chunks == worklist entries,
    strictly fewer than ``row_blocks × n_chunks`` on clustered fixtures
    (synthetic and a real partition);
  * ops-level selection (occupancy threshold, forced backends);
  * the boundary-aware ``greedy_partition`` halo term (weight-0 identity,
    positive weight reduces Σ|halo| at unchanged balance);
  * the GAT owner-shard projection dedup (pull-epoch forward equality vs
    the legacy per-subgraph projection, once-per-layer probe, strictly
    lower compiled-epoch FLOPs, projected cache layout).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import halo_exchange as hx
from repro.core import (TrainSettings, gat_projected, init_state,
                        make_epoch_fn, prepare_graph_data,
                        project_store_tables)
from repro.core.halo_exchange import HaloPrecision
from repro.graph import build_partitions, make_dataset
from repro.graph.partition import build_chunk_worklist, greedy_partition
from repro.kernels.spmm import (halo_spmm, halo_spmm_ref,
                                halo_spmm_skip_pallas, halo_spmm_skip_ref)
from repro.models.gnn import GNNConfig
from repro.optim import adam

pytestmark = pytest.mark.leg("m16-ppd2-hlo")


def _clustered_case(rng, rows, deg, ntab, feat, dtype=np.float32):
    """ELL refs clustered per 128-row block: block b references only a
    narrow slot band, so most (row_block, chunk) pairs are empty."""
    n_blocks = max(-(-rows // 128), 1)
    band = max((ntab - 1) // (2 * n_blocks), deg)
    lo = (rng.integers(0, 2, n_blocks) * (ntab - 1 - band)
          ).astype(np.int64)                   # band at the slab's ends
    nbr = np.empty((rows, deg), np.int64)
    for b in range(n_blocks):
        r0, r1 = b * 128, min((b + 1) * 128, rows)
        nbr[r0:r1] = rng.integers(lo[b], lo[b] + band, (r1 - r0, deg))
    nbr = nbr.astype(np.int32)
    wts = (rng.random((rows, deg)) * (nbr < ntab - 1)).astype(np.float32)
    table = rng.normal(size=(ntab, feat)).astype(dtype)
    table[-1] = 0
    return jnp.asarray(nbr), jnp.asarray(wts), jnp.asarray(table)


def _quantized(table, storage):
    data, scale = hx.quantize_rows(table, HaloPrecision(storage))
    data = np.asarray(data).copy()
    data[-1] = 0
    return jnp.asarray(data), scale


# ---------------------------------------------------------------------------
# Worklist builder
# ---------------------------------------------------------------------------

def test_worklist_covers_every_referenced_slot():
    """The masked oracle (only visited chunks accumulate) == the full
    oracle — i.e. the worklist misses nothing; a truncated worklist
    diverges, so the check has teeth."""
    rng = np.random.default_rng(0)
    for rows, deg, ntab, chunk in ((300, 7, 700, 128), (129, 3, 90, 32),
                                   (64, 5, 1000, 256)):
        nbr, wts, table = _clustered_case(rng, rows, deg, ntab, 48)
        wl = build_chunk_worklist(np.asarray(nbr), ntab, chunk)
        want = halo_spmm_ref(nbr, wts, table)
        got = halo_spmm_skip_ref(nbr, wts, table, None, wl.ids, wl.cnt,
                                 chunk)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # teeth: drop each block's last chunk → the oracle must change
    cut = halo_spmm_skip_ref(nbr, wts, table, None, wl.ids,
                             np.maximum(wl.cnt - 1, 0), chunk)
    assert not np.array_equal(np.asarray(cut), np.asarray(want))


def test_worklist_excludes_sentinel_and_pads_by_repeat():
    ntab, chunk = 512, 64
    nbr = np.full((128, 4), ntab - 1, np.int32)    # all sentinel
    nbr[0, 0] = 3
    nbr[5, 1] = 130                                 # chunks {0, 2}
    wl = build_chunk_worklist(nbr, ntab, chunk)
    assert wl.cnt.tolist() == [2]
    assert wl.ids[0, :2].tolist() == [0, 2]
    # padding repeats the last visited chunk (re-addresses resident VMEM)
    assert (wl.ids[0, 2:] == 2).all()
    # sentinel-only block → empty worklist
    wl0 = build_chunk_worklist(np.full((128, 4), ntab - 1, np.int32),
                               ntab, chunk)
    assert wl0.cnt.tolist() == [0] and wl0.max_chunks == 1
    assert wl0.occupancy == 0.0


def test_worklist_stacked_matches_per_subgraph():
    rng = np.random.default_rng(1)
    nbr = rng.integers(0, 200, (3, 256, 5)).astype(np.int32)
    wl = build_chunk_worklist(nbr, 201, 64)
    assert wl.ids.shape[0] == 3 and wl.cnt.shape == (3, 2)
    for m in range(3):
        wlm = build_chunk_worklist(nbr[m], 201, 64)
        assert wlm.cnt.tolist() == wl.cnt[m].tolist()
        np.testing.assert_array_equal(
            wlm.ids, wl.ids[m, :, :wlm.max_chunks])


# ---------------------------------------------------------------------------
# The skip kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("storage", ["fp32", "bf16", "int8"])
def test_skip_bitwise_equals_dense_stream(storage):
    """Chunk skipping == the dense stream, BITWISE, at every precision
    and ragged shapes: skipped chunks only ever contributed exact ±0.0."""
    rng = np.random.default_rng(11)
    for rows, deg, ntab, feat, chunk in ((300, 7, 700, 70, 128),
                                         (17, 3, 130, 33, 32)):
        nbr, wts, table = _clustered_case(rng, rows, deg, ntab, feat)
        data, scale = _quantized(table, storage)
        wl = build_chunk_worklist(np.asarray(nbr), ntab, chunk)
        skip = halo_spmm(nbr, wts, data, scale,
                         wl_ids=jnp.asarray(wl.ids),
                         wl_cnt=jnp.asarray(wl.cnt),
                         backend="pallas_skip_interpret", chunk_rows=chunk)
        dense = halo_spmm(nbr, wts, data, scale,
                          backend="pallas_stream_interpret",
                          chunk_rows=chunk)
        np.testing.assert_array_equal(np.asarray(skip), np.asarray(dense))
        # and tolerance-equal to the chunking-free oracle / resident path
        ref = halo_spmm_ref(nbr, wts, data, scale)
        np.testing.assert_allclose(np.asarray(skip), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)
        resident = halo_spmm(nbr, wts, data, scale,
                             backend="pallas_interpret")
        np.testing.assert_allclose(np.asarray(skip), np.asarray(resident),
                                   atol=1e-4, rtol=1e-4)


def test_skip_single_chunk_bitwise_resident():
    """One chunk spanning the slab → no reassociation at all: bitwise
    equal to the resident scaled kernel (same guarantee the dense stream
    pins in test_kernels_spmm)."""
    rng = np.random.default_rng(13)
    nbr, wts, table = _clustered_case(rng, 128, 4, 60, 128)
    data, scale = _quantized(table, "int8")
    wl = build_chunk_worklist(np.asarray(nbr), 60, 64)
    want = halo_spmm(nbr, wts, data, scale, backend="pallas_interpret")
    got = halo_spmm(nbr, wts, data, scale, wl_ids=jnp.asarray(wl.ids),
                    wl_cnt=jnp.asarray(wl.cnt),
                    backend="pallas_skip_interpret", chunk_rows=64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_skip_visited_chunks_equal_worklist_length():
    """Interpret-mode visit log: the kernel processes exactly the
    worklist's entries — NOT row_blocks × n_chunks — on a clustered
    synthetic fixture (and the padded steps are masked, id −1)."""
    rng = np.random.default_rng(17)
    rows, deg, ntab, feat, chunk = 384, 6, 1024, 128, 128
    nbr, wts, table = _clustered_case(rng, rows, deg, ntab, feat)
    wl = build_chunk_worklist(np.asarray(nbr), ntab, chunk)
    out, visits = halo_spmm_skip_pallas(
        nbr, wts, table, None, wl_ids=jnp.asarray(wl.ids),
        wl_cnt=jnp.asarray(wl.cnt), chunk_rows=chunk, interpret=True,
        count_visits=True)
    v = np.asarray(visits)
    assert (v >= 0).sum() == wl.visited_chunks
    assert wl.visited_chunks < wl.total_pairs, (wl.visited_chunks,
                                                wl.total_pairs)
    # logged ids are exactly the worklist prefix, in order
    for i in range(v.shape[0]):
        np.testing.assert_array_equal(v[i, :wl.cnt[i]],
                                      wl.ids[i, :wl.cnt[i]])
        assert (v[i, wl.cnt[i]:] == -1).all()
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(halo_spmm_ref(nbr, wts, table)),
                               atol=1e-4, rtol=1e-4)


def test_skip_visited_fewer_on_real_partition():
    """A real owner-grouped partition slab: the worklist is strictly
    sparser than the dense (row_blocks × chunks) schedule, and reading a
    pulled slab through it matches the oracle bitwise-vs-dense-stream."""
    g = make_dataset("flickr-sim", scale=0.25, seed=0)
    sp = build_partitions(g, 8)
    chunk = 64
    wl = sp.chunk_worklist(chunk)
    assert wl.visited_chunks < wl.total_pairs, (wl.visited_chunks,
                                                wl.total_pairs)
    # one subgraph's layer read: slab = pulled (H+1, hid) rows
    rng = np.random.default_rng(5)
    store = hx.init_store(1, sp.store_rows - 1, 32, HaloPrecision())
    reps = rng.normal(size=(sp.num_parts, 1, sp.part_size, 32)
                      ).astype(np.float32)
    store = hx.push(store, jnp.asarray(sp.local_slots),
                    jnp.asarray(sp.local_valid), jnp.asarray(reps),
                    jnp.asarray(sp.sentinel_slots))
    slab = hx.pull_slab(store, jnp.asarray(sp.halo_slots))
    m = 0
    data, scale = hx.layer_table({k: v[m] for k, v in slab.items()}, 0)
    nbr = jnp.asarray(sp.out_nbr[m])
    wts = jnp.asarray(sp.out_wts[m])
    skip = halo_spmm(nbr, wts, data, scale,
                     wl_ids=jnp.asarray(wl.ids[m]),
                     wl_cnt=jnp.asarray(wl.cnt[m]),
                     backend="pallas_skip_interpret", chunk_rows=chunk)
    dense = halo_spmm(nbr, wts, data, scale,
                      backend="pallas_stream_interpret", chunk_rows=chunk)
    np.testing.assert_array_equal(np.asarray(skip), np.asarray(dense))
    np.testing.assert_allclose(
        np.asarray(skip), np.asarray(halo_spmm_ref(nbr, wts, data, scale)),
        atol=1e-4, rtol=1e-4)


def test_skip_geometry_guard_and_selection():
    rng = np.random.default_rng(19)
    nbr, wts, table = _clustered_case(rng, 256, 4, 600, 64)
    wl = build_chunk_worklist(np.asarray(nbr), 600, 128)
    bad_ids = jnp.asarray(wl.ids[:1])        # wrong row-block count
    with pytest.raises(ValueError, match="worklist geometry"):
        halo_spmm(nbr, wts, table, None, wl_ids=bad_ids,
                  wl_cnt=jnp.asarray(wl.cnt[:1]),
                  backend="pallas_skip_interpret", chunk_rows=128)
    with pytest.raises(ValueError, match="needs the"):
        halo_spmm(nbr, wts, table, None, backend="pallas_skip_interpret")
    # finer-grained worklist than the call's chunk tiling → loud error
    # (the kernel would otherwise silently aggregate the wrong chunks)
    fine = build_chunk_worklist(np.asarray(nbr), 600, 32)
    assert fine.max_chunks > 600 // 512 + 1
    with pytest.raises(ValueError, match="chunk-geometry"):
        halo_spmm(nbr, wts, table, None, wl_ids=jnp.asarray(fine.ids),
                  wl_cnt=jnp.asarray(fine.cnt),
                  backend="pallas_skip_interpret", chunk_rows=512)
    # Auto-selection is static and occupancy-gated: with occupancy above
    # the threshold the (bogus) worklist must NOT be consulted; at or
    # below it, it is — the geometry guard makes the choice observable.
    halo_spmm(nbr, wts, table, None, wl_ids=bad_ids,
              wl_cnt=jnp.asarray(wl.cnt[:1]), backend="pallas_interpret",
              resident_max_bytes=1024, chunk_rows=128,
              occupancy=0.9, skip_occupancy_max=0.5)
    with pytest.raises(ValueError, match="worklist geometry"):
        halo_spmm(nbr, wts, table, None, wl_ids=bad_ids,
                  wl_cnt=jnp.asarray(wl.cnt[:1]),
                  backend="pallas_interpret", resident_max_bytes=1024,
                  chunk_rows=128, occupancy=0.3, skip_occupancy_max=0.5)
    # jnp backend ignores the worklist entirely.  Compared jitted against
    # jitted: XLA fuses the reference's multiply-reduce differently from
    # op-by-op execution, so eager vs jitted differs at the ULP level.
    out = halo_spmm(nbr, wts, table, None, wl_ids=bad_ids,
                    wl_cnt=jnp.asarray(wl.cnt[:1]), backend="jnp")
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jax.jit(halo_spmm_ref)(nbr, wts,
                                                           table)))
    # and any backend name outside the documented set is refused
    with pytest.raises(ValueError, match="unknown aggregation backend"):
        halo_spmm(nbr, wts, table, None, backend="pallas_interpet")


def test_worklist_build_vs_call_chunk_rows_guard():
    """The build-side knob (prepare_graph_data) and the call-side knob
    (GNNConfig.stream_chunk_rows) must agree — a coarser worklist than
    the kernel tiling would silently skip referenced rows, so the epoch
    entry points reject the mismatch loudly."""
    g = make_dataset("flickr-sim", scale=0.05, seed=2)
    data = prepare_graph_data(g, 2, stream_chunk_rows=1024)
    cfg = GNNConfig(model="gcn", num_layers=2, in_dim=g.features.shape[1],
                    hidden_dim=16, num_classes=int(g.labels.max()) + 1)
    with pytest.raises(ValueError, match="chunk_rows=1024"):
        init_state(cfg, adam(5e-3), data)      # call side defaults to 512
    # matching knobs pass
    init_state(dataclasses.replace(cfg, stream_chunk_rows=1024),
               adam(5e-3), data)


def test_prepare_graph_data_threads_worklist():
    g = make_dataset("flickr-sim", scale=0.1, seed=2)
    data = prepare_graph_data(g, 4, stream_chunk_rows=64)
    wl = data["_worklist"]
    assert 0.0 < wl.occupancy <= 1.0
    assert wl.chunk_rows == 64
    M, S, _ = data["struct"]["out_nbr"].shape
    assert data["struct"]["wl_ids"].shape[:2] == (M, max(-(-S // 128), 1))
    assert data["struct"]["wl_cnt"].shape == data["struct"][
        "wl_ids"].shape[:2]
    np.testing.assert_array_equal(np.asarray(data["struct"]["wl_ids"]),
                                  wl.ids)


# ---------------------------------------------------------------------------
# Boundary-aware partitioning score
# ---------------------------------------------------------------------------

def test_halo_weight_zero_preserves_assignments():
    g = make_dataset("flickr-sim", scale=0.1, seed=0)
    np.testing.assert_array_equal(greedy_partition(g, 4),
                                  greedy_partition(g, 4, halo_weight=0.0))


def test_halo_weight_reduces_halo_rows():
    """A positive marginal-halo weight lowers Σ_m |halo(G_m)| on the test
    graphs (partition_report's halo_rows) at unchanged balance."""
    from repro.graph import partition_report

    for ds, scale, M, w in (("flickr-sim", 0.25, 4, 0.25),
                            ("reddit-sim", 0.1, 8, 0.25)):
        g = make_dataset(ds, scale=scale, seed=0)
        base = partition_report(g, build_partitions(g, M))
        tuned = partition_report(g, build_partitions(g, M, halo_weight=w))
        assert tuned["halo_rows"] < base["halo_rows"], (ds, base, tuned)
        assert tuned["balance"] <= base["balance"] + 1e-6, (ds, base,
                                                           tuned)


# ---------------------------------------------------------------------------
# GAT owner-shard projection dedup
# ---------------------------------------------------------------------------

def _gat_setup(storage="fp32", dedup=True, interval=1):
    g = make_dataset("flickr-sim", scale=0.1, seed=4)
    data = prepare_graph_data(g, 4)
    cfg = GNNConfig(model="gat", num_layers=3, in_dim=g.features.shape[1],
                    hidden_dim=32, num_classes=int(g.labels.max()) + 1,
                    heads=2, gat_halo_dedup=dedup)
    settings = TrainSettings(sync_interval=interval, mode="digest",
                             precision=HaloPrecision(storage))
    return g, data, cfg, settings


def test_gat_dedup_pull_epoch_forward_equality():
    """At sync_interval=1 every epoch projects at the current W, so the
    dedup epoch's forward must equal the legacy per-subgraph projection
    (fp32 exact to reassociation; int8 re-quantizes z once).  From the
    next update on the trajectories may drift: the frozen projection
    rides the stale branch's stop_gradient, dropping the legacy path's
    W-gradient through the halo einsum — that is the documented
    semantics, not an accident."""
    for storage, atol in (("fp32", 1e-6), ("int8", 5e-3)):
        losses = {}
        for dedup in (True, False):
            g, data, cfg, settings = _gat_setup(storage, dedup)
            tdata = {k: v for k, v in data.items()
                     if not k.startswith("_")}
            opt = adam(5e-3)
            state = init_state(cfg, opt, data,
                               precision=settings.precision)
            fn = jax.jit(make_epoch_fn(cfg, opt, settings))
            tr = []
            for _ in range(2):
                state, m = fn(state, tdata)
                tr.append(float(m["loss"]))
            losses[dedup] = tr
        np.testing.assert_allclose(losses[True], losses[False], atol=atol,
                                   err_msg=storage)


def test_gat_dedup_projects_once_per_layer_and_cuts_flops():
    """project_store_tables emits exactly one (R, d)·W projection per
    hidden layer — R = owner shards × shard_rows, i.e. once per owner
    shard per layer — and the compiled dedup epoch costs strictly fewer
    FLOPs than the legacy epoch (which re-projects every subgraph's
    (H+1, d) slab every epoch)."""
    g, data, cfg, settings = _gat_setup("fp32", True, interval=2)
    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    opt = adam(5e-3)
    sp = data["_sp"]
    state = init_state(cfg, opt, data)
    zs = project_store_tables(state["store"], state["params"], cfg,
                              settings.precision)
    assert sorted(zs) == ["z0", "z1"]
    assert zs["z0"]["data"].shape == (1, sp.store_rows, cfg.hidden_dim)
    assert zs["z1"]["data"].shape == (1, sp.store_rows, cfg.num_classes)
    # once per layer: exactly L-1 projection contractions in the jaxpr
    jaxpr = jax.make_jaxpr(
        lambda s, p: project_store_tables(s, p, cfg, settings.precision))(
            state["store"], state["params"])
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name ==
            "dot_general"]
    assert len(dots) == cfg.num_layers - 1, jaxpr

    flops = {}
    for dedup in (True, False):
        cfg_d = dataclasses.replace(cfg, gat_halo_dedup=dedup)
        st = init_state(cfg_d, opt, data)
        fn = jax.jit(make_epoch_fn(cfg_d, opt, settings))
        cost = fn.lower(st, tdata).compile().cost_analysis()
        flops[dedup] = float(cost["flops"])
    assert flops[True] < flops[False], flops


def test_gat_dedup_cache_layout():
    g, data, cfg, _ = _gat_setup("int8", True)
    assert gat_projected(cfg)
    opt = adam(5e-3)
    state = init_state(cfg, opt, data, precision=HaloPrecision("int8"))
    M = int(data["halo_ids"].shape[0])
    H = int(data["halo_ids"].shape[1])
    cache = state["cache"]
    assert sorted(cache) == ["z0", "z0_scale", "z1", "z1_scale"]
    assert cache["z0"].shape == (M, 1, H + 1, cfg.hidden_dim)
    assert cache["z0"].dtype == jnp.int8
    assert cache["z1"].shape == (M, 1, H + 1, cfg.num_classes)
    assert cache["z1_scale"].shape == (M, 1, H + 1, 1)
    # legacy layout untouched
    cfg_l = dataclasses.replace(cfg, gat_halo_dedup=False)
    assert not gat_projected(cfg_l)
    state_l = init_state(cfg_l, opt, data, precision=HaloPrecision("int8"))
    assert sorted(state_l["cache"]) == ["data", "scale"]
