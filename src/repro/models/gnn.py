"""GNN models (GCN / GraphSAGE / GAT) in DIGEST's split-aggregation form.

Every layer implements Eq. 4/5 of the paper: the aggregation over neighbors
is split into an **in-subgraph** ELL product (fresh representations) and an
**out-of-subgraph** ELL product against whatever halo table the caller
supplies — fresh features (layer 0), *stale* representations (DIGEST),
zeros (partition-based baseline), or fresh remote reps (propagation-based
baseline).  The trainer chooses the table; the model is agnostic, which is
exactly what makes the baseline frameworks share 95% of the code path.

A halo table is either

  * a plain ``(H, d)`` array — per-subgraph tables (propagation baselines,
    direct model tests): aggregated through ``struct["out_nbr"]`` with a
    zero sentinel row appended at H; or
  * a **halo ref** dict ``{"data", "scale", "nbr", "wts"}`` — a shared
    slab (the HaloExchange compact store layer, or ``x_global`` for layer
    0) in storage precision plus the ELL indices *into that slab*.  The
    out-of-subgraph product then runs through the fused pull+aggregate
    kernel (:func:`repro.kernels.spmm.halo_spmm`): no per-subgraph halo
    table is ever materialized, and int8/bf16 rows are dequantized inside
    the kernel.  Under ``jax.vmap`` the slab enters unbatched, so slab-wide
    work (e.g. GAT's halo projection) is computed once, not per subgraph.

Shapes (single subgraph):
  x_local   (S, d)      padded local node features/reps
  x_halo    (H, d)      halo table for this layer's input (legacy form)
  in_nbr    (S, Din)    local slot ids, sentinel == S
  out_nbr   (S, Dout)   halo slot ids, sentinel == H
  ref[nbr]  (S, Dout)   slab row ids, sentinel == ref["data"].shape[0]-1
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels.spmm import halo_spmm, spmm
from repro.nn import ParamSpec, dense

Pytree = Any

# Named scopes of a layer's parts, nested in its ``layer_{ell}`` scope:
# they name every device op in the compiled program's op metadata, so a
# profile separates the split aggregation from the dense transforms and
# GAT's attention scores.
AGGREGATE_SCOPE = "aggregate"
TRANSFORM_SCOPE = "transform"
ATTENTION_SCOPE = "attention"


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class _StaticScalar:
    """A number carried through a pytree as aux data, not a leaf: it
    survives ``stop_gradient``/``vmap`` untouched and stays a plain
    Python float for the static-argument kernel knobs (``gamma`` keys
    the jit cache through ``halo_spmm``)."""
    value: float


def halo_ref(data: jax.Array, scale: Optional[jax.Array],
             nbr: jax.Array, wts: jax.Array,
             wl_ids: Optional[jax.Array] = None,
             wl_cnt: Optional[jax.Array] = None,
             pdata: Optional[jax.Array] = None,
             pscale: Optional[jax.Array] = None,
             gamma: float = 1.0) -> dict:
    """Bundle a shared halo slab (with sentinel zero row last) + indices.

    ``wl_ids``/``wl_cnt`` optionally carry the (row_block × chunk)
    occupancy worklist of this adjacency against the slab (see
    :class:`repro.graph.partition.ChunkWorklist`), enabling the chunk-
    skipping streamed kernel on the Pallas backends.

    ``pdata``/``pscale``/``gamma`` optionally carry the SAT predictor-
    history slab (``repro.core.predictor``) in the data slab's exact
    layout: the aggregation then reads the staleness-alleviated
    prediction ``dequant(data) + gamma·dequant(pdata)`` per row, fused
    into the kernel's dequant epilogue.  ``gamma`` is a static Python
    float (it keys the jit cache through ``halo_spmm``)."""
    ref = {"data": data, "nbr": nbr, "wts": wts}
    if scale is not None:
        ref["scale"] = scale
    if wl_ids is not None and wl_cnt is not None:
        ref["wl_ids"] = wl_ids
        ref["wl_cnt"] = wl_cnt
    if pdata is not None:
        ref["pdata"] = pdata
        ref["gamma"] = _StaticScalar(float(gamma))
        if pscale is not None:
            ref["pscale"] = pscale
    return ref


def projected_halo_ref(zdata: jax.Array, zscale: Optional[jax.Array],
                       nbr: jax.Array, wts: jax.Array) -> dict:
    """Bundle a *pre-projected* GAT halo table: rows are ``W·h̃`` (flat
    ``heads·head_dim`` wide, sentinel zero row last) computed once per
    owner shard at pull time, so the layer skips its per-subgraph slab
    projection entirely (see ``repro.core.digest`` and the GAT dedup
    notes in this module's layer code)."""
    ref = {"zdata": zdata, "nbr": nbr, "wts": wts}
    if zscale is not None:
        ref["zscale"] = zscale
    return ref


def _as_halo_ref(table, struct: dict) -> dict:
    """Normalize a legacy (H, d) table to the halo-ref form, picking up
    the adjacency's chunk worklist when the struct dict carries one."""
    if isinstance(table, dict):
        return table
    return halo_ref(_pad_sentinel(table), None,
                    struct["out_nbr"], struct["out_wts"],
                    struct.get("wl_ids"), struct.get("wl_cnt"))


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    model: str = "gcn"            # gcn | sage | gat
    num_layers: int = 3
    in_dim: int = 64
    hidden_dim: int = 128
    num_classes: int = 8
    heads: int = 4                # GAT only
    normalize: bool = True        # Algorithm 1 line 11 (L2 per node)
    residual: bool = False
    # Aggregation backend (repro.kernels.spmm.BACKENDS): "auto" runs the
    # kernel ladder on a TPU and the jnp reference elsewhere.
    backend: str = "auto"
    # -- halo_spmm ladder knobs (static; override the module constants
    # of repro.kernels.spmm.ops — None keeps the kernel defaults) -------
    stream_chunk_rows: Optional[int] = None    # STREAM_CHUNK_ROWS
    resident_max_bytes: Optional[int] = None   # RESIDENT_STRIPE_MAX_BYTES
    # GAT: project each owner shard's stale halo rows once per layer at
    # pull time and ship projected rows (True, the dedup path) instead of
    # re-projecting every subgraph's (H+1, d) slab every epoch (False,
    # the legacy ~M×-redundant path, kept for A/B cost comparison).
    gat_halo_dedup: bool = True

    @property
    def layer_dims(self) -> list[tuple[int, int]]:
        dims = []
        for ell in range(self.num_layers):
            din = self.in_dim if ell == 0 else self.hidden_dim
            dout = (self.num_classes if ell == self.num_layers - 1
                    else self.hidden_dim)
            dims.append((din, dout))
        return dims


def _pad_sentinel(x: jax.Array) -> jax.Array:
    """Append the zero sentinel row the ELL kernels gather for padding."""
    return jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)], axis=0)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def gnn_specs(cfg: GNNConfig) -> Pytree:
    specs: dict[str, Any] = {}
    for ell, (din, dout) in enumerate(cfg.layer_dims):
        layer: dict[str, Any] = {}
        if cfg.model == "gcn":
            layer["w"] = ParamSpec((din, dout), ("embed", "embed_out"))
            layer["b"] = ParamSpec((dout,), ("embed_out",), init="zeros")
        elif cfg.model == "sage":
            layer["w_self"] = ParamSpec((din, dout), ("embed", "embed_out"))
            layer["w_nbr"] = ParamSpec((din, dout), ("embed", "embed_out"))
            layer["b"] = ParamSpec((dout,), ("embed_out",), init="zeros")
        elif cfg.model == "gat":
            heads = cfg.heads if ell < cfg.num_layers - 1 else 1
            if dout % heads:
                raise ValueError(f"layer {ell}: dout {dout} % heads {heads}")
            dh = dout // heads
            layer["w"] = ParamSpec((din, heads, dh),
                                   ("embed", "heads", "head_dim"),
                                   fan_in_dims=(0,))
            layer["a_src"] = ParamSpec((heads, dh), ("heads", "head_dim"),
                                       init="normal")
            layer["a_dst"] = ParamSpec((heads, dh), ("heads", "head_dim"),
                                       init="normal")
            layer["b"] = ParamSpec((dout,), ("embed_out",), init="zeros")
        else:
            raise ValueError(cfg.model)
        specs[f"layer_{ell}"] = layer
    return specs


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def _halo_agg(cfg, ref: dict, wts: jax.Array) -> jax.Array:
    """Out-of-subgraph fused pull+aggregate with the config's ladder
    knobs (chunk size, VMEM budget) threaded into the rung selection in
    repro.kernels.spmm.ops."""
    g = ref.get("gamma")
    return halo_spmm(ref["nbr"], wts, ref["data"], ref.get("scale"),
                     wl_ids=ref.get("wl_ids"), wl_cnt=ref.get("wl_cnt"),
                     pdata=ref.get("pdata"), pscale=ref.get("pscale"),
                     gamma=g.value if g is not None else 1.0,
                     backend=cfg.backend,
                     resident_max_bytes=cfg.resident_max_bytes,
                     chunk_rows=cfg.stream_chunk_rows)


def _gcn_layer(cfg, p, x_local, x_halo, struct) -> jax.Array:
    with jax.named_scope(AGGREGATE_SCOPE):
        ref = _as_halo_ref(x_halo, struct)
        agg = spmm(struct["in_nbr"], struct["in_wts"],
                   _pad_sentinel(x_local), backend=cfg.backend)
        agg = agg + _halo_agg(cfg, ref, ref["wts"])
    with jax.named_scope(TRANSFORM_SCOPE):
        return dense(agg, p["w"], p["b"])


def _sage_layer(cfg, p, x_local, x_halo, struct) -> jax.Array:
    # Mean aggregator: row-normalize the (GCN) weights to a mean.
    with jax.named_scope(AGGREGATE_SCOPE):
        ref = _as_halo_ref(x_halo, struct)
        in_w, out_w = struct["in_wts"], ref["wts"]
        denom = jnp.sum(in_w, axis=1, keepdims=True) + jnp.sum(
            out_w, axis=1, keepdims=True)
        denom = jnp.maximum(denom, 1e-12)
        agg = spmm(struct["in_nbr"], in_w / denom, _pad_sentinel(x_local),
                   backend=cfg.backend)
        agg = agg + _halo_agg(cfg, ref, out_w / denom)
    with jax.named_scope(TRANSFORM_SCOPE):
        return (dense(x_local, p["w_self"]) + dense(agg, p["w_nbr"])
                + p["b"])


def _multihead_spmm(nbr, att, z_pad, backend):
    """(S, D, heads) attention × (T, heads, dh) tables → (S, heads·dh).

    One batched aggregation (vmap over the head axis) instead of a Python
    loop of per-head spmm calls — compiles to a single kernel launch per
    adjacency side.
    """
    per_head = jax.vmap(lambda a, z: spmm(nbr, a, z, backend=backend),
                        in_axes=(2, 1), out_axes=1)
    out = per_head(att, z_pad)                    # (S, heads, dh)
    return out.reshape(out.shape[0], -1)


def _gat_layer(cfg, p, x_local, x_halo, struct) -> jax.Array:
    S = x_local.shape[0]
    ref = _as_halo_ref(x_halo, struct)
    heads, dh = p["a_src"].shape
    with jax.named_scope(TRANSFORM_SCOPE):
        z_loc = jnp.einsum("sd,dhk->shk", x_local, p["w"])  # (S, heads, dh)
        if "zdata" in ref:
            # Pre-projected halo table (projected_halo_ref): rows are
            # already W·h̃, projected ONCE per owner shard at pull time
            # instead of once per subgraph per epoch — the owner-shard
            # dedup path.  Only the (cheap) attention scores below still
            # use this epoch's a_src.
            z_out = ref["zdata"].astype(jnp.float32)
            if "zscale" in ref:
                z_out = z_out * ref["zscale"]
            T = z_out.shape[0]                    # slab rows incl. sentinel
            z_out = z_out.reshape(T, heads, dh)
        else:
            # Legacy: dequantize the raw halo rows and project here.  When
            # the slab enters vmap unbatched (a shared store slab) this
            # happens once for all subgraphs; with device-local
            # per-subgraph slabs it is the M×-redundant projection the
            # dedup path removes.
            x_out = ref["data"].astype(jnp.float32)
            if "scale" in ref:
                x_out = x_out * ref["scale"]
            if "pdata" in ref:
                # SAT prediction before projection — exact by linearity
                # of W.
                p_out = ref["pdata"].astype(jnp.float32)
                if "pscale" in ref:
                    p_out = p_out * ref["pscale"]
                x_out = x_out + jnp.float32(ref["gamma"].value) * p_out
            T = x_out.shape[0]                    # slab rows incl. sentinel
            z_out = jnp.einsum("sd,dhk->shk", x_out,
                               p["w"])            # (T, heads, dh)

    with jax.named_scope(ATTENTION_SCOPE):
        s_dst = jnp.einsum("shk,hk->sh", z_loc, p["a_dst"])   # (S, heads)
        src_loc = jnp.einsum("shk,hk->sh", z_loc, p["a_src"])  # (S, heads)
        src_out = jnp.einsum("shk,hk->sh", z_out, p["a_src"])  # (T, heads)

        def _scores(nbr, src_table, n_cols):
            s_src = jnp.take(src_table, nbr, axis=0)       # (S, D, heads)
            e = jax.nn.leaky_relu(s_dst[:, None, :] + s_src, 0.2)
            valid = (nbr < n_cols)[..., None]
            return jnp.where(valid, e, -1e30), valid

        src_loc_pad = jnp.concatenate(
            [src_loc, jnp.zeros((1, heads), src_loc.dtype)], 0)
        e_in, v_in = _scores(struct["in_nbr"], src_loc_pad, S)
        e_out, v_out = _scores(ref["nbr"], src_out, T - 1)

        m = jnp.maximum(jnp.max(e_in, axis=1), jnp.max(e_out, axis=1))
        m = jax.lax.stop_gradient(m)                       # (S, heads)
        p_in = jnp.exp(e_in - m[:, None, :]) * v_in
        p_out = jnp.exp(e_out - m[:, None, :]) * v_out
        denom = (jnp.sum(p_in, axis=1) + jnp.sum(p_out, axis=1) + 1e-16)
        a_in = p_in / denom[:, None, :]                    # (S, Din, heads)
        a_out = p_out / denom[:, None, :]

    with jax.named_scope(AGGREGATE_SCOPE):
        z_loc_pad = jnp.concatenate(
            [z_loc, jnp.zeros((1,) + z_loc.shape[1:], z_loc.dtype)], 0)
        out = _multihead_spmm(struct["in_nbr"], a_in, z_loc_pad,
                              cfg.backend)
        out = out + _multihead_spmm(ref["nbr"], a_out, z_out, cfg.backend)
    with jax.named_scope(TRANSFORM_SCOPE):
        return out + p["b"]


_LAYERS = {"gcn": _gcn_layer, "sage": _sage_layer, "gat": _gat_layer}


# ---------------------------------------------------------------------------
# Sampled (control-variate) layer variants — the mini-batch regime
# ---------------------------------------------------------------------------
#
# VR-GCN estimator (arXiv 1710.10568) at the ELL-weight level: with
# edge_scale = deg/n_sampled at sampled entries (0 elsewhere),
#
#   w_fresh = in_wts · edge_scale        (scaled sampled neighbors, fresh)
#   w_resid = in_wts − w_fresh           (everything else, historical)
#   agg_in  = spmm(w_fresh, h) + spmm(w_resid, h̄)
#           = spmm(in_wts, h̄) + Σ_sampled scale·in_wts·(h − h̄)
#
# i.e. history-of-all-neighbors plus the inverse-inclusion-scaled fresh
# minus-stale correction on the sample — unbiased in the sample, and with
# fanout >= deg the scale is exactly 1.0 so w_fresh == in_wts bitwise and
# w_resid == +0.0: the estimator IS the full-batch aggregation.  The
# out-of-subgraph side always reads the stale store (pure history — its
# own control variate), riding the fused halo_spmm path unchanged.

def _cv_weights(in_wts: jax.Array, samp: dict) -> tuple:
    w_fresh = in_wts * samp["edge_scale"]
    return w_fresh, in_wts - w_fresh


def _gcn_layer_cv(cfg, p, x_local, h_hist, x_halo, struct, samp):
    with jax.named_scope(AGGREGATE_SCOPE):
        ref = _as_halo_ref(x_halo, struct)
        w_fresh, w_resid = _cv_weights(struct["in_wts"], samp)
        agg = spmm(struct["in_nbr"], w_fresh, _pad_sentinel(x_local),
                   backend=cfg.backend)
        agg = agg + spmm(struct["in_nbr"], w_resid, _pad_sentinel(h_hist),
                         backend=cfg.backend)
        agg = agg + _halo_agg(cfg, ref, ref["wts"])
    with jax.named_scope(TRANSFORM_SCOPE):
        return dense(agg, p["w"], p["b"])


def _sage_layer_cv(cfg, p, x_local, h_hist, x_halo, struct, samp):
    # Same full-neighborhood mean denominator as _sage_layer: the CV
    # split redistributes the numerator, not the normalization.
    with jax.named_scope(AGGREGATE_SCOPE):
        ref = _as_halo_ref(x_halo, struct)
        in_w, out_w = struct["in_wts"], ref["wts"]
        denom = jnp.sum(in_w, axis=1, keepdims=True) + jnp.sum(
            out_w, axis=1, keepdims=True)
        denom = jnp.maximum(denom, 1e-12)
        w_fresh, w_resid = _cv_weights(in_w, samp)
        agg = spmm(struct["in_nbr"], w_fresh / denom,
                   _pad_sentinel(x_local), backend=cfg.backend)
        agg = agg + spmm(struct["in_nbr"], w_resid / denom,
                         _pad_sentinel(h_hist), backend=cfg.backend)
        agg = agg + _halo_agg(cfg, ref, out_w / denom)
    with jax.named_scope(TRANSFORM_SCOPE):
        return (dense(x_local, p["w_self"]) + dense(agg, p["w_nbr"])
                + p["b"])


def sampled_struct(struct: dict, samp: dict, sentinel: int) -> dict:
    """GAT fallback view: unsampled in-ELL entries remapped to the zero
    sentinel, so the layer runs full attention over the sampled rows only
    (attention renormalizes per destination — no inclusion scaling; and
    no control variate, since the nonlinear score has no additive
    history decomposition).  With fanout >= deg this is the identity
    remap: unsampled entries are exactly the sentinel entries already."""
    out = dict(struct)
    out["in_nbr"] = jnp.where(samp["edge_keep"], struct["in_nbr"],
                              sentinel)
    return out


def gnn_layer(cfg: GNNConfig, layer_params: Pytree, x_local: jax.Array,
              x_halo, struct: dict) -> jax.Array:
    """Run ONE split-aggregation layer — the public single-layer entry.

    ``layer_params`` is one ``params[f"layer_{ell}"]`` subtree; the rest
    of the contract matches the per-layer step inside
    :func:`gnn_forward` (x_halo is a plain table or a halo ref).  The
    serving path (``repro.core.serving``) uses this to run just the top
    layer over rows read back from the owner-sharded store, instead of
    replaying the whole forward.
    """
    return _LAYERS[cfg.model](cfg, layer_params, x_local, x_halo, struct)


# ---------------------------------------------------------------------------
# Full forward (single subgraph)
# ---------------------------------------------------------------------------

def gnn_forward(cfg: GNNConfig, params: Pytree, x_local: jax.Array,
                halo_tables: list[jax.Array], struct: dict,
                ) -> tuple[jax.Array, list[jax.Array]]:
    """Run the L-layer GNN on one subgraph.

    Args:
      x_local: (S, in_dim) local node features.
      halo_tables: per-layer halo input tables; halo_tables[ℓ] feeds layer ℓ
        (ℓ=0 is raw halo features; ℓ≥1 are stale hidden reps of width
        hidden_dim — this is the DIGEST pull result).
      struct: ELL adjacency dict (in_nbr/in_wts/out_nbr/out_wts).
    Returns:
      (logits (S, num_classes), reps) where reps[ℓ] is the layer-(ℓ+1) input
      representation this subgraph would *push* to the stale store
      (post-activation, post-normalization hidden states, ℓ = 0..L-2).
    """
    layer_fn = _LAYERS[cfg.model]
    h = x_local
    push: list[jax.Array] = []
    for ell in range(cfg.num_layers):
        p = params[f"layer_{ell}"]
        # The scope names the layer in the compiled program's op metadata.
        with jax.named_scope(f"layer_{ell}"):
            out = layer_fn(cfg, p, h, halo_tables[ell], struct)
        h = _finish_layer(cfg, out, h, ell, push)
    return h, push


def _finish_layer(cfg: GNNConfig, out: jax.Array, h: jax.Array, ell: int,
                  push: list) -> jax.Array:
    """Post-layer tail shared by the full-batch and sampled forwards:
    relu + Algorithm-1 line-11 normalize (+ optional residual) on hidden
    layers, recording the layer's PUSH representation."""
    if ell < cfg.num_layers - 1:
        out = jax.nn.relu(out)
        if cfg.normalize:   # Algorithm 1 line 11
            out = out / jnp.maximum(
                jnp.linalg.norm(out, axis=-1, keepdims=True), 1e-12)
        if cfg.residual and out.shape == h.shape:
            out = out + h
        push.append(out)
    return out


def gnn_forward_sampled(cfg: GNNConfig, params: Pytree, x_local: jax.Array,
                        halo_tables: list, hist_tables: list, struct: dict,
                        samp: dict) -> tuple[jax.Array, list[jax.Array]]:
    """Sampled (mini-batch) L-layer forward with stale-history control
    variates — the VR-GCN estimator over DIGEST's split aggregation.

    Layer 0 aggregates in full: its "history" is the raw features, which
    are exact, so the CV estimate degenerates to the exact sum — sampling
    it would only add variance.  Hidden layers ℓ >= 1 aggregate sampled
    in-subgraph neighbors fresh and the complement from
    ``hist_tables[ℓ-1]`` (the device-local last-step representations of
    this subgraph's own rows, same (S, hidden) row space as ``x_local``);
    the out-of-subgraph side reads the pulled stale slab in
    ``halo_tables`` — history by construction — through the unchanged
    fused halo_spmm path.  ``samp`` is one subgraph's slice of a
    :class:`repro.graph.sampler.NeighborSampler` batch
    (``edge_scale``/``edge_keep``).  GAT has no additive decomposition of
    its attention scores, so it falls back to full in-batch attention
    over the sampled rows (``sampled_struct``; no control variate).

    With ``fanout >= max degree`` this reproduces :func:`gnn_forward`
    bitwise for gcn/sage (the residual weights are exactly +0.0) and to
    float tolerance for gat (identical remapped ELL).
    """
    h = x_local
    push: list[jax.Array] = []
    for ell in range(cfg.num_layers):
        p = params[f"layer_{ell}"]
        if ell == 0:
            out = _LAYERS[cfg.model](cfg, p, h, halo_tables[0], struct)
        elif cfg.model == "gat":
            out = _gat_layer(cfg, p, h, halo_tables[ell],
                             sampled_struct(struct, samp,
                                            x_local.shape[0]))
        elif cfg.model == "gcn":
            out = _gcn_layer_cv(cfg, p, h, hist_tables[ell - 1],
                                halo_tables[ell], struct, samp)
        else:
            out = _sage_layer_cv(cfg, p, h, hist_tables[ell - 1],
                                 halo_tables[ell], struct, samp)
        h = _finish_layer(cfg, out, h, ell, push)
    return h, push
