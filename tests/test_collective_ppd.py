"""Parts-per-device > 1 collective paths: equivalence + loud mismatch.

``collective_pull`` / ``shard_push`` / ``shard_staleness_error`` now
block the owner-sharded slot space into k = M/devices shards per device.
These tests pin down, for M in {4, 8} on 2- and 4-device meshes (k in
{1, 2, 4}) plus the M=16-on-8 acceptance case:

  * pull slabs, pushed stores and staleness maxima are **bitwise** equal
    to the dense-gather/SPMD fallback forms, in fp32 and int8;
  * a full collective-mode epoch leaves a store bitwise-equal to the
    gather fallback's (gcn/sage), and the r=2 pulled slab (reading the
    r=1 store) is bitwise-equal too; both agree with single-device
    execution to a few float32 ULPs (``hlo_utils.tree_close``: the
    partitioner re-fuses the forward's reductions) — gat to 1e-6, its
    multi-head attention einsums reassociate under vmap;
  * a part count that does not divide the mesh axis raises the
    spelled-out ValueError instead of corrupting slot math.

Needs >= 8 forced host devices; on single-device hosts the subprocess
variant re-launches this file (same pattern as test_sharded_pull).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.leg("m16-ppd2-hlo")


def _tree_equal(a: dict, b: dict, what: str = ""):
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"{what}[{k}]")


def _kvs_parity(g, M: int, D: int):
    """collective_pull / shard_push / shard_staleness_error == the dense
    fallback forms, bitwise, with k = M/D owner shards per device."""
    from repro.core import halo_exchange as hx
    from repro.core.halo_exchange import HaloPrecision
    from repro.graph import build_partitions
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=D)
    sp = build_partitions(g, M)
    assert sp.shards_per_device(D) == M // D
    L1, hid = 2, 32
    rng = np.random.default_rng(M * 31 + D)
    reps = rng.normal(size=(M, L1, sp.part_size, hid)).astype(np.float32)
    slots = jnp.asarray(sp.local_slots)
    valid = jnp.asarray(sp.local_valid)
    sent = jnp.asarray(sp.sentinel_slots)
    boundary = jnp.asarray(sp.local_boundary)
    plan = sp.pull_plan()

    for storage in ("fp32", "int8"):
        prec = HaloPrecision(storage)
        store = hx.init_store(L1, sp.store_rows - 1, hid, prec)
        store = hx.push(store, slots, valid, jnp.asarray(reps), sent)

        want = hx.pull_slab(store, jnp.asarray(sp.halo_slots))
        got = hx.collective_pull(store, jnp.asarray(plan.send_offsets),
                                 jnp.asarray(plan.recv_positions),
                                 sp.halo_size, mesh)
        _tree_equal(got, want, f"pull M={M} D={D} {storage}")

        base = hx.init_store(L1, sp.store_rows - 1, hid, prec)
        via_spmd = hx.push(base, slots, valid, jnp.asarray(reps), sent)
        via_shmap = hx.shard_push(base, slots, valid, jnp.asarray(reps),
                                  sp.shard_rows, mesh)
        _tree_equal(via_shmap, via_spmd, f"push M={M} D={D} {storage}")

        fresh = jnp.asarray(
            rng.normal(size=reps.shape).astype(np.float32))
        eps_spmd = hx.staleness_error(store, fresh, slots, boundary)
        eps_shmap = hx.shard_staleness_error(store, fresh, slots,
                                             boundary, sp.shard_rows,
                                             mesh)
        np.testing.assert_array_equal(np.asarray(eps_shmap),
                                      np.asarray(eps_spmd))


def _epoch_equivalence(g, M: int, D: int, model: str, storage: str,
                       exact: bool):
    """Two epochs (push at r=1, pull at r=2 with N=2): post-epoch stores
    and the r=2 pulled slab agree across single-device execution, the
    sharded gather fallback, and the fully-SPMD collective epoch."""
    import hlo_utils
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=D)
    runs = {}
    for name, m, pull_mode in (("single", None, "gather"),
                               ("gather", mesh, "gather"),
                               ("collective", mesh, "collective")):
        fn, state, tdata = hlo_utils.make_epoch(
            g, M, m, storage=storage, pull_mode=pull_mode, model=model)
        state, m1 = fn(state, tdata)     # r=1: PUSH fresh reps
        store1 = {k: np.asarray(v) for k, v in state["store"].items()}
        state, m2 = fn(state, tdata)     # r=2: PULL the r=1 store
        runs[name] = {
            "store": store1,
            "slab": {k: np.asarray(v) for k, v in state["cache"].items()},
            "eps": np.asarray(m1["staleness_eps"]),
        }

    ref = runs["single"]
    for name in ("gather", "collective"):
        got = runs[name]
        label = f"{model}/{storage} M={M} D={D} {name}"
        if exact:
            # Single-device vs partitioned: few-ULP agreement (see
            # hlo_utils.tree_close); the routing is pinned bitwise below.
            hlo_utils.tree_close(got["store"], ref["store"],
                                 f"store {label}")
            hlo_utils.tree_close(got["slab"], ref["slab"], f"slab {label}")
            hlo_utils.tree_close({"eps": got["eps"]}, {"eps": ref["eps"]},
                                 label)
        else:
            for k in ref["store"]:
                np.testing.assert_allclose(
                    got["store"][k].astype(np.float32),
                    ref["store"][k].astype(np.float32),
                    atol=1e-6, err_msg=f"store {label}")
            for k in ref["slab"]:
                np.testing.assert_allclose(
                    got["slab"][k].astype(np.float32),
                    ref["slab"][k].astype(np.float32),
                    atol=1e-6, err_msg=f"slab {label}")
    # The two sharded paths against each other (the acceptance check:
    # collective == dense-gather fallback, bitwise).
    if exact:
        _tree_equal(runs["collective"]["store"], runs["gather"]["store"],
                    f"store {model}/{storage} M={M} D={D} coll-vs-gather")
        _tree_equal(runs["collective"]["slab"], runs["gather"]["slab"],
                    f"slab {model}/{storage} M={M} D={D} coll-vs-gather")


def _mismatch_raises(g):
    from repro.core import halo_exchange as hx
    from repro.core.halo_exchange import HaloPrecision
    from repro.graph import build_partitions
    from repro.launch.mesh import make_host_mesh

    mesh3 = make_host_mesh(data=3)
    sp = build_partitions(g, 4)
    plan = sp.pull_plan()
    store = hx.init_store(2, sp.store_rows - 1, 16, HaloPrecision())
    for fn, args in (
            (hx.collective_pull, (store, jnp.asarray(plan.send_offsets),
                                  jnp.asarray(plan.recv_positions),
                                  sp.halo_size, mesh3)),
            (hx.shard_push, (store, jnp.asarray(sp.local_slots),
                             jnp.asarray(sp.local_valid),
                             jnp.zeros((4, 2, sp.part_size, 16)),
                             sp.shard_rows, mesh3)),
            (hx.shard_staleness_error,
             (store, jnp.zeros((4, 2, sp.part_size, 16)),
              jnp.asarray(sp.local_slots),
              jnp.asarray(sp.local_boundary), sp.shard_rows, mesh3))):
        try:
            fn(*args)
        except ValueError as e:
            msg = str(e)
            assert "num_parts=4" in msg and "3 devices" in msg, msg
        else:
            raise AssertionError(f"{fn.__name__} accepted M=4 on a "
                                 f"3-device axis")
    try:
        sp.shards_per_device(3)
    except ValueError as e:
        assert "num_parts=4" in str(e) and "3 devices" in str(e)
    else:
        raise AssertionError("shards_per_device accepted 4 % 3")


def _checks():
    from repro.graph import make_dataset

    assert jax.device_count() >= 8, jax.device_count()
    g = make_dataset("flickr-sim", scale=0.1, seed=7)

    for M in (4, 8):
        for D in (2, 4):
            _kvs_parity(g, M, D)
    _mismatch_raises(g)

    # Full-epoch equivalence: gcn/sage bitwise across the sharded paths
    # and to a few ULPs of single-device execution, gat to 1e-6, at
    # parts-per-device 2 — and the M=16-on-8-devices acceptance case.
    _epoch_equivalence(g, 8, 4, "gcn", "fp32", exact=True)
    _epoch_equivalence(g, 8, 4, "sage", "int8", exact=True)
    _epoch_equivalence(g, 8, 4, "gat", "fp32", exact=False)
    _epoch_equivalence(g, 16, 8, "gcn", "int8", exact=True)


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (CI REPRO_HOST_DEVICES=8 job)")
def test_collective_parts_per_device_inprocess():
    _checks()


def test_collective_parts_per_device_subprocess():
    """Force an 8-device CPU platform in a subprocess so the
    parts-per-device paths are exercised even on single-device hosts."""
    if jax.device_count() >= 8:
        pytest.skip("covered by the in-process variant")
    import hlo_utils
    hlo_utils.run_forced_device_subprocess(__file__, "PPD_OK")


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    _checks()
    print("PPD_OK")
