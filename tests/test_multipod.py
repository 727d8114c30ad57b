"""Multi-pod collective halo exchange: equivalence + 2D-mesh HLO census.

``pull_mode="collective"`` auto-detects a mesh "pod" axis and runs the
two-stage exchange — intra-pod ragged ``all_to_all`` over "data", then
``pods - 1`` inter-pod ``ppermute`` rounds over "pod" (see the routing
section of ``repro.core.halo_exchange``).  On a forced 8-device host
shaped as ("pod", "data") = (2, 4), these tests pin down:

  * pulls, pushes and the Theorem-1 staleness probe are **bitwise**
    equal across the dense-gather fallback, the single-pod collective
    (flat data=8 mesh) and the multi-pod collective, for M in {8, 16}
    (k = parts/device in {1, 2}) in fp32 and int8;
  * two full epochs (PUSH at r=1, PULL at r=2) leave stores, pulled
    slabs and staleness maxima equal across single-device execution,
    the sharded gather fallback, the single-pod collective and the
    multi-pod collective — multi-pod vs single-pod collective bitwise,
    each vs single-device execution to a few float32 ULPs
    (``hlo_utils.tree_close``) for gcn/sage and to 1e-6 for gat;
  * the compiled multi-pod epoch's collective census, **per mesh
    axis**: the pull all-to-alls ride only "data" groups (intra-pod),
    the permutes only "pod" pairs (inter-pod), with exact counts
    (``expected_all_to_all`` / ``expected_collective_permute``) and
    ZERO all-gather / reduce-scatter anywhere;
  * an M that is not a multiple of pods·data raises the spelled-out
    ValueError from every collective entry point (and from
    ``check_collective_geometry``) instead of corrupting slot math.

Needs >= 8 forced host devices; on single-device hosts the subprocess
variant re-launches this file (same pattern as test_collective_ppd).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.leg("multipod-2x4")


def _tree_equal(a: dict, b: dict, what: str = ""):
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"{what}[{k}]")


def _pod_mesh(pods: int = 2, data: int = 4):
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(pod=pods, data=data, model=1)


def _kvs_parity(g, M: int, pods: int, D: int):
    """collective_pull / shard_push / shard_staleness_error on the
    ("pod", "data") = (pods, D) mesh == the flat data=pods·D collective
    == the dense fallback forms, bitwise, with k = M/(pods·D) shards
    per device."""
    from repro.core import halo_exchange as hx
    from repro.core.halo_exchange import HaloPrecision
    from repro.graph import build_partitions
    from repro.launch.mesh import make_host_mesh

    pod_mesh = _pod_mesh(pods, D)
    flat_mesh = make_host_mesh(data=pods * D)
    assert hx.exchange_axes(pod_mesh) == ("pod", "data")
    assert hx.exchange_axes(flat_mesh) == ("data",)
    assert hx.exchange_size(pod_mesh) == pods * D

    sp = build_partitions(g, M)
    k = M // (pods * D)
    assert hx.shards_per_device(M, pod_mesh) == k
    L1, hid = 2, 32
    rng = np.random.default_rng(M * 131 + pods)
    reps = jnp.asarray(
        rng.normal(size=(M, L1, sp.part_size, hid)).astype(np.float32))
    slots = jnp.asarray(sp.local_slots)
    valid = jnp.asarray(sp.local_valid)
    sent = jnp.asarray(sp.sentinel_slots)
    boundary = jnp.asarray(sp.local_boundary)
    plan = sp.pull_plan()
    send = jnp.asarray(plan.send_offsets)
    recv = jnp.asarray(plan.recv_positions)

    for storage in ("fp32", "int8"):
        prec = HaloPrecision(storage)
        label = f"M={M} ({pods}x{D}) {storage}"
        store = hx.init_store(L1, sp.store_rows - 1, hid, prec)
        store = hx.push(store, slots, valid, reps, sent)

        want = hx.pull_slab(store, jnp.asarray(sp.halo_slots))
        got_pod = hx.collective_pull(store, send, recv, sp.halo_size,
                                     pod_mesh)
        got_flat = hx.collective_pull(store, send, recv, sp.halo_size,
                                      flat_mesh)
        _tree_equal(got_pod, want, f"pull-pod-vs-gather {label}")
        _tree_equal(got_pod, got_flat, f"pull-pod-vs-flat {label}")

        base = hx.init_store(L1, sp.store_rows - 1, hid, prec)
        via_spmd = hx.push(base, slots, valid, reps, sent)
        via_pod = hx.shard_push(base, slots, valid, reps, sp.shard_rows,
                                pod_mesh)
        via_flat = hx.shard_push(base, slots, valid, reps, sp.shard_rows,
                                 flat_mesh)
        _tree_equal(via_pod, via_spmd, f"push-pod-vs-spmd {label}")
        _tree_equal(via_pod, via_flat, f"push-pod-vs-flat {label}")

        fresh = jnp.asarray(
            rng.normal(size=reps.shape).astype(np.float32))
        eps_ref = hx.staleness_error(store, fresh, slots, boundary)
        eps_pod = hx.shard_staleness_error(store, fresh, slots, boundary,
                                           sp.shard_rows, pod_mesh)
        np.testing.assert_array_equal(np.asarray(eps_pod),
                                      np.asarray(eps_ref),
                                      err_msg=f"staleness {label}")


def _epoch_equivalence(g, M: int, model: str, storage: str, exact: bool):
    """Two epochs: post-epoch stores, the r=2 pulled slab and the r=1
    staleness maxima agree across single-device execution, the sharded
    gather fallback, the single-pod collective and the multi-pod
    collective (the acceptance check)."""
    import hlo_utils
    from repro.launch.mesh import make_host_mesh

    pod_mesh = _pod_mesh()
    flat_mesh = make_host_mesh(data=8)
    runs = {}
    for name, m, pull_mode in (("single", None, "gather"),
                               ("gather", pod_mesh, "gather"),
                               ("flat", flat_mesh, "collective"),
                               ("multipod", pod_mesh, "collective")):
        fn, state, tdata = hlo_utils.make_epoch(
            g, M, m, storage=storage, pull_mode=pull_mode, model=model)
        state, m1 = fn(state, tdata)     # r=1: PUSH fresh reps
        store1 = {k: np.asarray(v) for k, v in state["store"].items()}
        state, _ = fn(state, tdata)      # r=2: PULL the r=1 store
        runs[name] = {
            "store": store1,
            "slab": {k: np.asarray(v) for k, v in state["cache"].items()},
            "eps": np.asarray(m1["staleness_eps"]),
        }

    ref = runs["single"]
    for name in ("gather", "flat", "multipod"):
        got = runs[name]
        label = f"{model}/{storage} M={M} {name}"
        if exact:
            # Single-device vs partitioned: few-ULP agreement (see
            # hlo_utils.tree_close); the sharded paths are pinned bitwise
            # against each other below.
            hlo_utils.tree_close(got["store"], ref["store"],
                                 f"store {label}")
            hlo_utils.tree_close(got["slab"], ref["slab"], f"slab {label}")
            hlo_utils.tree_close({"eps": got["eps"]}, {"eps": ref["eps"]},
                                 label)
        else:
            for part in ("store", "slab"):
                for k in ref[part]:
                    np.testing.assert_allclose(
                        got[part][k].astype(np.float32),
                        ref[part][k].astype(np.float32),
                        atol=1e-6, err_msg=f"{part} {label}")
    # Multi-pod vs the single-pod collective: bitwise on every model —
    # the two-stage exchange reorders only the transport, never values.
    _tree_equal(runs["multipod"]["store"], runs["flat"]["store"],
                f"store {model}/{storage} M={M} multipod-vs-flat")
    _tree_equal(runs["multipod"]["slab"], runs["flat"]["slab"],
                f"slab {model}/{storage} M={M} multipod-vs-flat")
    np.testing.assert_array_equal(runs["multipod"]["eps"],
                                  runs["flat"]["eps"],
                                  err_msg=f"{model} multipod-vs-flat eps")


def _hlo_census(g):
    """Per-axis census of the compiled multi-pod epoch: all-to-alls ride
    "data" only, permutes ride "pod" only, counts exact, zero
    all-gather / reduce-scatter; the gather fallback on the same mesh is
    the positive control (all-gathers, no all-to-all)."""
    import hlo_utils

    pods = 2
    mesh = _pod_mesh(pods, 4)
    for M, storage, model in ((8, "fp32", "gcn"), (16, "int8", "gcn"),
                              (8, "int8", "gat")):
        compiled = hlo_utils.compile_epoch(
            g, M, mesh, storage=storage, pull_mode="collective",
            model=model)
        text = compiled.as_text()
        c = hlo_utils.collective_counts(text)
        census = hlo_utils.collective_axis_census(text, mesh)
        label = f"multipod M={M} {model}/{storage}"
        assert c["all-gather"] == 0, (label, c)
        assert c["reduce-scatter"] == 0, (label, c)
        want_a2a = hlo_utils.expected_all_to_all(storage, model=model)
        want_cp = hlo_utils.expected_collective_permute(storage, pods,
                                                        model=model)
        assert c["all-to-all"] == want_a2a, (label, c)
        assert c["collective-permute"] == want_cp, (label, c)
        # Stage 1 must stay inside the pod, stage 2 must touch only the
        # pod axis — neither may widen to the combined axes.
        assert census["all-to-all"] == {("data",): want_a2a}, (
            label, census)
        assert census["collective-permute"] == {("pod",): want_cp}, (
            label, census)
        assert census["all-gather"] == {}, (label, census)
        assert sum(census["all-reduce"].values()) == c["all-reduce"] > 0, (
            label, census)

    compiled = hlo_utils.compile_epoch(g, 8, mesh, storage="fp32",
                                       pull_mode="gather")
    c = hlo_utils.collective_counts(compiled.as_text())
    assert c["all-gather"] > 0, c
    assert c["all-to-all"] == 0, c


def _mismatch_raises(g):
    """M not a multiple of pods·data → the spelled-out ValueError from
    every collective entry point; the message names both counts."""
    from repro.core import check_collective_geometry, prepare_graph_data
    from repro.core import halo_exchange as hx
    from repro.core.halo_exchange import HaloPrecision
    from repro.graph import build_partitions

    mesh = _pod_mesh(2, 4)                    # 8 exchange devices
    M = 12                                    # 12 % 8 != 0
    sp = build_partitions(g, M)
    plan = sp.pull_plan()
    store = hx.init_store(2, sp.store_rows - 1, 16, HaloPrecision())
    zeros = jnp.zeros((M, 2, sp.part_size, 16))
    for fn, args in (
            (hx.collective_pull, (store, jnp.asarray(plan.send_offsets),
                                  jnp.asarray(plan.recv_positions),
                                  sp.halo_size, mesh)),
            (hx.shard_push, (store, jnp.asarray(sp.local_slots),
                             jnp.asarray(sp.local_valid), zeros,
                             sp.shard_rows, mesh)),
            (hx.shard_staleness_error,
             (store, zeros, jnp.asarray(sp.local_slots),
              jnp.asarray(sp.local_boundary), sp.shard_rows, mesh))):
        with pytest.raises(ValueError) as e:
            fn(*args)
        msg = str(e.value)
        assert "num_parts=12" in msg and "8 devices" in msg, msg
        assert "pod" in msg, msg            # names the multi-pod layout
    data = prepare_graph_data(g, M)
    with pytest.raises(ValueError) as e:
        check_collective_geometry(data, mesh)
    assert "num_parts=12" in str(e.value), str(e.value)
    # Sanity: the same M works on a mesh whose axes it divides.
    assert check_collective_geometry(data, _pod_mesh(2, 2)) == 3


def _checks():
    from repro.graph import make_dataset

    assert jax.device_count() >= 8, jax.device_count()
    g = make_dataset("flickr-sim", scale=0.1, seed=11)

    for M in (8, 16):                         # k = 1 and 2 per device
        _kvs_parity(g, M, 2, 4)
    _mismatch_raises(g)
    _hlo_census(g)

    # Full-epoch equivalence incl. the acceptance case: multi-pod
    # collective bitwise-equal to the single-pod collective, and both
    # and the gather fallback within a few ULPs of single-device
    # execution (gcn/sage; gat to 1e-6).
    _epoch_equivalence(g, 8, "gcn", "fp32", exact=True)
    _epoch_equivalence(g, 16, "sage", "int8", exact=True)
    _epoch_equivalence(g, 8, "gat", "fp32", exact=False)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (CI REPRO_HOST_DEVICES=8 job)")
def test_multipod_collective_inprocess():
    _checks()


def test_multipod_collective_subprocess():
    """Force an 8-device CPU platform in a subprocess so the multi-pod
    paths are exercised even on single-device hosts."""
    if jax.device_count() >= 8:
        pytest.skip("covered by the in-process variant")
    import hlo_utils
    hlo_utils.run_forced_device_subprocess(__file__, "MULTIPOD_OK")


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    _checks()
    print("MULTIPOD_OK")
