"""Compile the main-path aggregation kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  Each case lowers one Pallas kernel of the
``halo_spmm`` ladder (the resident rung, and the one-hot dense and
chunk-skipping streams by name; fp32 and int8) at real widths — hidden
128, chunk 512 — and asserts that the kernel survived into the
executable (``tpu_custom_call``) and that its buffers fit one chip.  A
kernel that only ever ran in interpret mode can pass every CPU test and
still be refused by Mosaic; these cases catch that before any chip time
is spent.  The row-gather rung is compiled at the products-sim ×10
cell's shapes, with a guard on its temporary memory.

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

from repro.kernels.spmm import (BLOCK_ROWS, halo_spmm, multihead_spmm,
                                select_kernel)

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


def _compile(args, backend="pallas", **static):
    fn = jax.jit(functools.partial(halo_spmm, backend=backend,
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


# (kernel, table rows, storage dtype, scales?, backend, rung the ladder
# selects for the table)
CASES = [
    ("resident", 2048, jnp.float32, False, "pallas", "resident"),
    ("resident", 2048, jnp.int8, True, "pallas", "resident"),
    ("stream", 8192, jnp.float32, False, "pallas_stream", "gather"),
    ("stream", 8192, jnp.int8, True, "pallas_stream", "gather"),
    ("skip", 8192, jnp.float32, False, "pallas_skip", "gather"),
    ("skip", 8192, jnp.int8, True, "pallas_skip", "gather"),
]


@pytest.mark.parametrize("kernel,n_tab,dtype,scale,backend,rung", CASES,
                         ids=[f"{c[0]}-{jnp.dtype(c[2]).name}"
                              for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, no_persistent_cache, kernel,
                                 n_tab, dtype, scale, backend, rung):
    """Each Pallas kernel compiles: the resident one as the ladder
    selects it, the one-hot streams by name (past the budget the ladder
    gathers)."""
    assert select_kernel((n_tab, HIDDEN), dtype, has_scale=scale) == rung
    _compile(_args(one_chip, n_tab, dtype, scale=scale,
                   worklist=kernel == "skip"), backend=backend)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["float32", "int8"])
def test_predictor_epilogue_compiles_for_v5e(one_chip, no_persistent_cache,
                                             dtype):
    """The SAT epilogue's second product over the same one-hot tile, in
    the one-hot stream kernel (by name: the ladder gathers this table)."""
    scale = dtype == jnp.int8
    args = _args(one_chip, 8192, dtype, scale=scale, pred=True)
    _compile(args, backend="pallas_stream", gamma=0.5)


def test_kernel_backward_compiles_for_v5e(one_chip, no_persistent_cache):
    """The epoch differentiates through the in-subgraph aggregation: the
    forward rung the ladder selects (the gather, for this table) and the
    reference VJP compile together, batched over subgraphs as the epoch's
    vmap batches them."""
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
    assert "jit(halo_spmm_gather)" in compiled.as_text()


# The products-sim ×10 cell's aggregation calls: 8 parts of 15,752 rows;
# the halo side's width-80 ELL against its 104,249-row slab, the local
# side's width-48 ELL against its 15,753-row table.
CELL_PARTS, CELL_ROWS = 8, 15752


@pytest.mark.parametrize("deg,n_tab", [(80, 104249), (48, 15753)],
                         ids=["halo", "local"])
def test_gather_rung_compiles_for_v5e(one_chip, no_persistent_cache, deg,
                                      n_tab):
    """The row gather at the cell's shapes, vmapped over the parts as the
    epoch runs it.  Its temporary memory stays under one ``(parts, rows,
    deg, 128)`` float32 intermediate, the whole set of gathered rows that
    the plain take-then-sum form keeps live (5.2 GB on the halo side)."""
    assert select_kernel((n_tab, HIDDEN), jnp.float32) == "gather"

    def sds(shape, dt):
        return jax.ShapeDtypeStruct((CELL_PARTS,) + shape, dt,
                                    sharding=one_chip)
    fn = jax.vmap(functools.partial(halo_spmm, backend="pallas"))
    compiled = jax.jit(fn).lower(
        sds((CELL_ROWS, deg), jnp.int32), sds((CELL_ROWS, deg), jnp.float32),
        sds((n_tab, HIDDEN), jnp.float32)).compile()
    text = compiled.as_text()
    assert "jit(halo_spmm_gather)" in text
    assert "tpu_custom_call" not in text
    whole = CELL_PARTS * CELL_ROWS * deg * HIDDEN * 4
    assert compiled.memory_analysis().temp_size_in_bytes < whole


# The GAT cell's aggregation calls (Flickr, 8 parts of 11,720 rows, 4
# heads of 32): the halo side's width-24 ELL against its 53,753-row slab
# (attention gradient only: the stale rows take none), the local side's
# width-16 ELL against its 11,721-row table (both gradients).
GAT_ROWS, GAT_HEADS = 11720, 4


@pytest.mark.parametrize("deg,n_tab,table_grad", [(24, 53753, False),
                                                  (16, 11721, True)],
                         ids=["halo", "local"])
def test_multihead_aggregation_compiles_for_v5e(one_chip,
                                                no_persistent_cache, deg,
                                                n_tab, table_grad):
    """``multihead_spmm`` forward and backward at the GAT cell's shapes,
    vmapped over the parts as the epoch runs it.  No Pallas call, and its
    temporary memory stays under one ``(parts, rows, deg, 128)`` float32
    intermediate (a vmap of the one-head aggregation over the heads took
    8.4 GB and 6.2 GB here)."""
    def loss(att, table, nbr):
        out = jax.vmap(multihead_spmm)(nbr, att, table)
        return jnp.sum(out * out)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct((CELL_PARTS,) + shape, dt,
                                    sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1) if table_grad else 0)
                       ).lower(sds((GAT_ROWS, deg, GAT_HEADS), jnp.float32),
                               sds((n_tab, HIDDEN), jnp.float32),
                               sds((GAT_ROWS, deg), jnp.int32)).compile()
    text = compiled.as_text()
    assert "jit(multihead_spmm)" in text
    assert "tpu_custom_call" not in text
    whole = CELL_PARTS * GAT_ROWS * deg * HIDDEN * 4
    assert compiled.memory_analysis().temp_size_in_bytes < whole


def test_gat_epoch_compiles_for_v5e_below_the_resident_budget(
        one_chip, no_persistent_cache):
    """The paper's GAT (3 x 128, 4 heads) on flickr-sim's own 2,200 nodes
    in 8 parts: every table fits the resident budget, where a vmap of the
    one-head kernel over the heads handed Mosaic a block it refused.  The
    multi-head aggregation runs no Pallas kernel, so the epoch compiles;
    the GAT layers' aggregation holds no Pallas call."""
    from repro.core import (HaloPrecision, TrainSettings, init_state,
                            make_epoch_fn, prepare_graph_data)
    from repro.graph import make_dataset
    from repro.launch.train_gnn import model_config
    from repro.optim import adam

    g = make_dataset("flickr-sim")
    data = prepare_graph_data(g, 8)
    cfg = model_config(g, data, model="gat", hidden_dim=HIDDEN,
                       num_layers=3, heads=4, backend="pallas")
    assert select_kernel((data["halo_ids"].shape[1] + 1, 32),
                         jnp.float32) == "resident"
    opt = adam(5e-3)
    settings = TrainSettings(sync_interval=10, mode="digest",
                             pull_mode="gather",
                             precision=HaloPrecision("fp32"))
    state = jax.eval_shape(lambda: init_state(cfg, opt, data,
                                              precision=settings.precision))
    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    on_chip = lambda tree: jax.tree.map(lambda _: one_chip, tree)
    epoch = jax.jit(make_epoch_fn(cfg, opt, settings))
    text = epoch.lower(_placed(state, on_chip(state)),
                       _placed(tdata, on_chip(tdata))).compile().as_text()
    assert "jit(multihead_spmm)" in text
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert not any("multihead" in line for line in kernels)


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


@pytest.mark.parametrize("storage", ["fp32", "int8"])
def test_gather_rung_compiles_in_shard_map_for_v5e_2x2(
        mesh4, small_graph, no_persistent_cache, storage):
    """The collective epoch with the halo tables past a shrunken resident
    budget (the in-subgraph tables keep the default one): the row gather
    runs inside the manual ``shard_map`` with its varying-axes types
    checked, an int8 store dequantized before it."""
    import dataclasses

    from repro.core import (HaloPrecision, TrainSettings, init_state,
                            make_epoch_fn)
    from repro.launch.train_gnn import subgraph_shardings
    from repro.optim import adam

    data, cfg = small_graph
    cfg = dataclasses.replace(cfg, resident_max_bytes=1024)
    opt = adam(5e-3)
    settings = TrainSettings(sync_interval=10, mode="digest",
                             pull_mode="collective",
                             precision=HaloPrecision(storage))
    state = jax.eval_shape(lambda: init_state(cfg, opt, data,
                                              precision=settings.precision))
    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    data_sh, state_sh = subgraph_shardings(tdata, state, mesh4)
    epoch = jax.jit(make_epoch_fn(cfg, opt, settings, mesh=mesh4),
                    in_shardings=(state_sh, data_sh))
    text = epoch.lower(_placed(state, state_sh),
                       _placed(tdata, data_sh)).compile().as_text()
    whiles = [line for line in text.splitlines()
              if " while(" in line and "jit(halo_spmm_gather)" in line]
    assert len(whiles) == cfg.num_layers
    assert "all-to-all" in text
