"""Compile the main-path aggregation kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  Each case lowers one rung of the ``halo_spmm``
ladder (resident / dense stream / chunk-skipping stream, fp32 and int8)
at real widths — hidden 128, chunk 512 — and asserts that the Pallas
kernel survived into the executable (``tpu_custom_call``) and that its
buffers fit one chip.  A kernel that only ever ran in interpret mode can
pass every CPU test and still be refused by Mosaic; these cases catch
that before any chip time is spent.

The four-device cases compile, over the described 2x2 mesh, each
program that aggregates while its operands are sharded: the collective
epoch, the full-graph evaluation and the sharded serving query.  XLA
cannot partition a Pallas kernel, so each must hold its kernels inside a
``shard_map``; one that does not is refused at lowering.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and a worker that loads
it while collecting would leave the others failing.  All such compiles
live in this one file, so exactly one test worker loads the library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.spmm import BLOCK_ROWS, halo_spmm, select_kernel

V5E_HBM_BYTES = 16 * 1024**3
ROWS, DEG, HIDDEN, CHUNK = 4096, 32, 128, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache(topo):
    """A described-topology compile can be written to the persistent
    cache but not read back without a chip; keep these compiles out of
    it so later ones do not warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _args(one_chip, n_tab, dtype, *, scale, pred=False, worklist=False):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = {"nbr": sds((ROWS, DEG), jnp.int32),
            "wts": sds((ROWS, DEG), jnp.float32),
            "data": sds((n_tab, HIDDEN), dtype)}
    if scale:
        args["scale"] = sds((n_tab, 1), jnp.float32)
    if pred:
        args["pdata"] = sds((n_tab, HIDDEN), dtype)
        if scale:
            args["pscale"] = sds((n_tab, 1), jnp.float32)
    if worklist:
        n_chunks = -(-n_tab // CHUNK)
        args["wl_ids"] = sds((ROWS // BLOCK_ROWS, n_chunks), jnp.int32)
        args["wl_cnt"] = sds((ROWS // BLOCK_ROWS,), jnp.int32)
    return args


def _compile(args, **static):
    fn = jax.jit(functools.partial(halo_spmm, backend="pallas",
                                   chunk_rows=CHUNK, **static))
    names = list(args)
    compiled = jax.jit(lambda *a: fn(**dict(zip(names, a)))).lower(
        *args.values()).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, mem
    return compiled


# (rung, table rows, storage dtype, scales?, static knobs)
CASES = [
    ("resident", 2048, jnp.float32, False, {}),
    ("resident", 2048, jnp.int8, True, {}),
    ("stream", 8192, jnp.float32, False, {}),
    ("stream", 8192, jnp.int8, True, {}),
    ("skip", 8192, jnp.float32, False, {"occupancy": 0.25}),
    ("skip", 8192, jnp.int8, True, {"occupancy": 0.25}),
]


@pytest.mark.parametrize("rung,n_tab,dtype,scale,static", CASES,
                         ids=[f"{c[0]}-{jnp.dtype(c[2]).name}"
                              for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, no_persistent_cache, rung,
                                 n_tab, dtype, scale, static):
    worklist = rung == "skip"
    assert select_kernel((n_tab, HIDDEN), dtype, has_scale=scale,
                         has_worklist=worklist,
                         occupancy=static.get("occupancy")) == rung
    _compile(_args(one_chip, n_tab, dtype, scale=scale,
                   worklist=worklist), **static)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["float32", "int8"])
def test_predictor_epilogue_compiles_for_v5e(one_chip, no_persistent_cache,
                                             dtype):
    """The SAT epilogue's second product over the same one-hot tile."""
    scale = dtype == jnp.int8
    args = _args(one_chip, 8192, dtype, scale=scale, pred=True)
    _compile(args, gamma=0.5)


def test_kernel_backward_compiles_for_v5e(one_chip, no_persistent_cache):
    """The epoch differentiates through the in-subgraph aggregation: the
    forward kernel and the reference VJP compile together, batched over
    subgraphs as the epoch's vmap batches them."""
    m = 2

    def loss(wts, table, nbr):
        out = jax.vmap(lambda n, w, t: halo_spmm(n, w, t, backend="pallas",
                                                 chunk_rows=CHUNK))(
            nbr, wts, table)
        return jnp.sum(out * out)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        sds((m, ROWS, DEG), jnp.float32), sds((m, 8192, HIDDEN),
                                              jnp.float32),
        sds((m, ROWS, DEG), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- four devices: the sharded programs ------------------------------------

@pytest.fixture(scope="module")
def mesh4(topo):
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(data=4, devices=topo.devices[:4])


@pytest.fixture(scope="module")
def small_graph():
    """8 parts of a small products-sim graph with the paper's GCN
    (hidden 128) on the compiled kernels: k = 2 parts per device."""
    from repro.core import prepare_graph_data
    from repro.graph import make_dataset
    from repro.launch.train_gnn import model_config

    g = make_dataset("products-sim", scale=0.05)
    data = prepare_graph_data(g, 8)
    cfg = model_config(g, data, model="gcn", hidden_dim=HIDDEN,
                       num_layers=3, backend="pallas")
    return data, cfg


def _placed(tree, shardings):
    """Abstract arguments carrying their shardings."""
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        np.shape(x), x.dtype, sharding=s), tree, shardings)


def _holds_kernels(compiled, at_least: int):
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= at_least
    return text


def test_collective_epoch_compiles_for_v5e_2x2(mesh4, small_graph,
                                              no_persistent_cache):
    from repro.core import (HaloPrecision, TrainSettings, init_state,
                            make_epoch_fn)
    from repro.launch.train_gnn import subgraph_shardings
    from repro.optim import adam

    data, cfg = small_graph
    opt = adam(5e-3)
    settings = TrainSettings(sync_interval=10, mode="digest",
                             pull_mode="collective",
                             precision=HaloPrecision("int8"))
    state = jax.eval_shape(lambda: init_state(cfg, opt, data,
                                              precision=settings.precision))
    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    data_sh, state_sh = subgraph_shardings(tdata, state, mesh4)
    epoch = jax.jit(make_epoch_fn(cfg, opt, settings, mesh=mesh4),
                    in_shardings=(state_sh, data_sh))
    text = _holds_kernels(epoch.lower(_placed(state, state_sh),
                                      _placed(tdata, data_sh)).compile(),
                          2 * cfg.num_layers)
    assert "all-to-all" in text


def test_evaluate_compiles_for_v5e_2x2(mesh4, small_graph,
                                      no_persistent_cache):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import evaluate, init_state
    from repro.launch.train_gnn import subgraph_shardings
    from repro.optim import adam

    data, cfg = small_graph
    state = jax.eval_shape(lambda: init_state(cfg, adam(5e-3), data))
    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    data_sh, _ = subgraph_shardings(tdata, state, mesh4)
    rep = NamedSharding(mesh4, P())
    params = _placed(state["params"],
                     jax.tree.map(lambda _: rep, state["params"]))
    # At least each layer's in-graph aggregation: the empty-halo calls
    # of layers >= 1 are identical, and XLA merges them.
    _holds_kernels(evaluate.lower(cfg, params, _placed(tdata, data_sh),
                                  mesh4).compile(), cfg.num_layers)


@pytest.mark.parametrize("storage", ["fp32", "int8"])
def test_sharded_query_compiles_for_v5e_2x2(mesh4, small_graph,
                                           no_persistent_cache, storage):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import serving
    from repro.models.gnn import gnn_specs
    from repro.nn import init_params

    data, cfg = small_graph
    plan = serving.build_serve_plan(data)
    scfg = serving.ServeConfig(batch_size=64, storage=storage,
                               backend="pallas")
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                gnn_specs(cfg)))
    store = jax.eval_shape(lambda: serving.init_serve_store(
        plan, cfg.hidden_dim, scfg.precision))
    sdata = plan.sharded_data(data)
    store_sh, sdata_sh, q_sh = serving.serve_shardings(store, sdata, mesh4)
    rep = NamedSharding(mesh4, P())
    q_rows = jax.ShapeDtypeStruct((plan.num_parts, scfg.batch_size),
                                  jnp.int32, sharding=q_sh)
    compiled = serving.serve_query_sharded.lower(
        cfg, scfg, mesh4, plan.halo_size,
        _placed(params, jax.tree.map(lambda _: rep, params)),
        _placed(store, store_sh), _placed(sdata, sdata_sh),
        q_rows).compile()
    # The in-shard and the pulled-halo aggregation of the top layer.
    _holds_kernels(compiled, 2)
