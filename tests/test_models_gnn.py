"""GNN split-aggregation exactness: fresh halo == full-graph forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.digest import full_graph_forward, prepare_graph_data
from repro.core.error_bound import fresh_halo_cache
from repro.graph import make_dataset
from repro.models.gnn import GNNConfig, gnn_forward, gnn_specs
from repro.nn import init_params


@pytest.mark.parametrize("model", ["gcn", "sage", "gat"])
def test_distributed_fresh_equals_full_graph(model):
    """With FRESH halo tables (propagation mode), the partitioned forward
    must reproduce the full-graph forward exactly — the paper's 'no
    information loss' claim for its split formulation (Eq. 4/5)."""
    g = make_dataset("flickr-sim", scale=0.1)
    data = prepare_graph_data(g, 3)
    cfg = GNNConfig(model=model, num_layers=2,
                    in_dim=g.features.shape[1], hidden_dim=32,
                    num_classes=int(g.labels.max()) + 1, heads=4)
    params = init_params(jax.random.PRNGKey(0), gnn_specs(cfg))

    full_logits, _ = full_graph_forward(cfg, params, data)
    fresh = fresh_halo_cache(cfg, params, data)          # (M, L-1, H, hid)

    M = data["halo_ids"].shape[0]
    x_local = data["x_global"][data["local_ids"]]
    x_halo0 = data["x_global"][data["halo_ids"]]
    for m in range(M):
        struct = {k: v[m] for k, v in data["struct"].items()}
        tables = [x_halo0[m]] + [fresh[m][i]
                                 for i in range(cfg.num_layers - 1)]
        logits_m, _ = gnn_forward(cfg, params, x_local[m], tables, struct)
        # map back to full-graph row order
        loc = np.asarray(data["local_ids"][m])
        valid = np.asarray(data["local_valid"][m])
        got = np.asarray(logits_m)[valid]
        want = np.asarray(full_logits)[loc[valid]]
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("model", ["gcn", "sage", "gat"])
def test_zero_slab_ref_equals_zero_tables(model):
    """Partition-mode semantics: a zeroed shared slab (halo-ref form, real
    ELL weights) must equal the legacy zeroed per-part tables — dropped
    neighbors still count as zero vectors in GAT's attention denominator
    and SAGE's mean, they don't vanish from the normalization."""
    from repro.models.gnn import halo_ref

    g = make_dataset("flickr-sim", scale=0.05)
    data = prepare_graph_data(g, 2)
    cfg = GNNConfig(model=model, num_layers=2, in_dim=g.features.shape[1],
                    hidden_dim=32, num_classes=int(g.labels.max()) + 1,
                    heads=4)
    params = init_params(jax.random.PRNGKey(1), gnn_specs(cfg))
    m = 0
    x_local = data["x_global"][data["local_ids"]][m]
    struct = {k: v[m] for k, v in data["struct"].items()}
    H = data["halo_ids"].shape[1]
    B = int(data["store_ids"].shape[0]) - 1

    legacy_tables = [jnp.zeros((H, cfg.in_dim))] + \
        [jnp.zeros((H, cfg.hidden_dim))] * (cfg.num_layers - 1)
    want, _ = gnn_forward(cfg, params, x_local, legacy_tables, struct)

    n1 = data["x_global"].shape[0]
    sp = data["_sp"]
    refs = [halo_ref(jnp.zeros((n1, cfg.in_dim)), None,
                     jnp.asarray(sp.out_nbr_global[m]),
                     struct["out_wts"])] + \
        [halo_ref(jnp.zeros((B + 1, cfg.hidden_dim)), None,
                  jnp.asarray(sp.out_nbr_store[m]),
                  struct["out_wts"])] * \
        (cfg.num_layers - 1)
    got, _ = gnn_forward(cfg, params, x_local, refs, struct)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


def test_normalization_applied():
    g = make_dataset("flickr-sim", scale=0.05)
    data = prepare_graph_data(g, 2)
    cfg = GNNConfig(model="gcn", num_layers=3, in_dim=g.features.shape[1],
                    hidden_dim=16, num_classes=4, normalize=True)
    params = init_params(jax.random.PRNGKey(0), gnn_specs(cfg))
    x_local = data["x_global"][data["local_ids"]][0]
    tables = [data["x_global"][data["halo_ids"]][0]] + [
        jnp.zeros((data["halo_ids"].shape[1], 16))] * 2
    struct = {k: v[0] for k, v in data["struct"].items()}
    _, push = gnn_forward(cfg, params, x_local, tables, struct)
    for rep in push:
        norms = np.asarray(jnp.linalg.norm(rep, axis=-1))
        nonzero = norms[norms > 1e-6]     # padding rows stay zero
        assert len(nonzero) > 0
        assert np.abs(nonzero - 1.0).max() < 1e-3  # unit rows


def test_gat_gather_backend_matches_the_oracle_in_loss_and_gradient(
        monkeypatch):
    """GAT's multi-head aggregation (the slot scan with its own backward,
    which every backend runs, the chip's included) gives one subgraph's
    loss and every parameter's gradient as its plain take-then-sum oracle
    with autodiff's VJP does."""
    from repro.core.digest import make_subgraph_loss
    from repro.kernels.spmm import multihead_spmm_ref
    from repro.models import gnn

    g = make_dataset("flickr-sim", scale=0.1)
    data = prepare_graph_data(g, 3)
    cfg = GNNConfig(model="gat", num_layers=3, in_dim=g.features.shape[1],
                    hidden_dim=32, num_classes=int(g.labels.max()) + 1,
                    heads=4)
    params = init_params(jax.random.PRNGKey(2), gnn_specs(cfg))
    fresh = fresh_halo_cache(cfg, params, data)
    m = 1
    x_local = data["x_global"][data["local_ids"]][m]
    tables = [data["x_global"][data["halo_ids"]][m]] + [
        fresh[m][i] for i in range(cfg.num_layers - 1)]
    struct = {k: v[m] for k, v in data["struct"].items()}
    got = {}
    for form in ("program", "oracle"):
        if form == "oracle":
            monkeypatch.setattr(gnn, "multihead_spmm", multihead_spmm_ref)
        (loss, _), grads = jax.value_and_grad(
            make_subgraph_loss(cfg), has_aux=True)(
            params, x_local, tables, struct, data["labels"][m],
            data["train_mask"][m])
        got[form] = (loss, grads)
    np.testing.assert_allclose(got["program"][0], got["oracle"][0],
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got["program"][1]),
                    jax.tree.leaves(got["oracle"][1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
