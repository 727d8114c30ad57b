"""Online embedding serving over the owner-sharded DIGEST store.

ROADMAP's "store as a product" path: the stale-representation KVS
already holds everything needed to answer node-prediction queries
(recommendations / fraud scores) — h^(L-1), the input rows of the top
GNN layer.  This module turns it into a read-optimized inference
service: a jitted batched query engine over an **all-node** serving
store, a device-resident hot-row cache for skewed (Zipf) traffic, and a
donation-friendly in-place refresh so serving and periodic DIGEST sync
coexist without doubling store memory.

Serving-store layout
--------------------

Training stores only *boundary* rows; a query can hit any node, so the
serving store is a second, single-layer owner-sharded slab over ALL
nodes, reusing every HaloExchange convention (and therefore every
pull/push/quantize code path):

    slot(v) = assign[v] · (S + 1) + local_row(v)

with S the padded part size, one zero sentinel row per shard at local
row S, and the global sentinel the last row (``serve_map[N] = R - 1``).
Two consequences do the heavy lifting:

  * shard m, in local-row order, IS part m's ``x_local`` table for the
    top layer — ``store["data"][0].reshape(M, S+1, hidden)`` is a
    collective-free re-view under pjit (the slot axis splits into the
    sharded part axis times local rows), sentinel row included exactly
    where ``_pad_sentinel`` would put it;
  * ``owner = slot // (S+1)`` — so the generic
    :func:`repro.graph.partition.build_pull_plan` routes the serving
    pull, and :func:`halo_exchange.collective_pull` ships out-of-shard
    rows through the same ragged ``all_to_all`` as training (zero
    all-gathers, pinned by the HLO census in tests/test_serving.py).

The store dict carries one extra leaf next to {"data"[, "scale"]}: an
int32 ``version`` scalar, bumped by every refresh — the cache
invalidation signal (below).

Query engines
-------------

:func:`serve_query` — the single-program fast path: a batch of global
node ids is resolved through ``serve_map``, the (L-1)-layer rows of
each query node and its in-neighbors are gathered from the store (the
gcn/sage neighbor reduction rides :func:`repro.kernels.spmm.halo_spmm`,
i.e. the resident/stream/skip kernel-selection ladder; GAT's attention
gathers rows through :func:`repro.kernels.spmm.halo_gather`), and only
the top layer runs — logits for exactly the queried rows.  The
aggregation mirrors the full-graph forward's ELL math term for term, so
served gcn/sage logits are bitwise equal to
``full_graph_forward``/``evaluate()`` on a frozen store (gat ≤ 1e-6,
attention softmax reassociation).

:func:`serve_query_sharded` — the SPMD form over a mesh: per-part local
row batches, out-of-shard halo rows pulled via ``collective_pull`` with
the serving PullPlan, in-shard rows read from the device's own slab
re-view, the top layer run per part on the device that holds the part.
Same split-aggregation (in + out) form as the training epoch.

Hot-row cache
-------------

A fixed-capacity, set-associative (``cache_ways``-way, LRU) slot cache
in front of the store, holding the **maximally-collapsed** hot row — a
query node's finished logits row, the pure function of (slot, store
version) that a repeat query needs.  Entries carry (tag = serve slot,
version); a hit requires both to match, so a refresh invalidates every
cached row by bumping ``version`` — no scanning, no eviction sweep.
Lookup and miss-fill are fully vectorized: one gather for the lookup,
one deterministic scatter for the fill (at most one fill per set per
batch; the winner is picked by a scatter-max over batch indices, so the
tag and data writes can never interleave rows).  Hit/miss counters
count valid queries only.

Refresh
-------

:func:`make_refresh_fn` returns a jitted ``refresh(store, reps_top,
rdata)`` with ``donate_argnums=(0,)``: the old store's buffers are
donated, so XLA scatters the new representations in place — serving
and periodic sync share one store-sized allocation.  ``reps_top`` is
:func:`repro.core.digest.top_layer_reps` (byte-for-byte the tensor a
training PUSH writes for layer L-2), routed through the same
``halo_exchange.push`` / ``shard_push`` scatter as training.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import halo_exchange
from repro.core.halo_exchange import PRECISIONS, HaloPrecision
from repro.graph.partition import PullPlan, build_pull_plan
from repro.kernels.spmm import halo_gather, halo_spmm, vma_checkable
from repro.nn import dense


# ---------------------------------------------------------------------------
# Static serving knobs (jit-cache keys)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static serving knobs — a frozen, hashable jit-cache key.

    Every field is part of the compiled program (batch geometry, cache
    geometry, storage precision, kernel-selection knobs), so the whole
    config is passed through ``static_argnames`` like the PR-4 kernel
    knobs: a benchmark sweeping capacity / batch / precision retraces
    exactly when it must and can never reuse a wrong executable.
    """

    batch_size: int = 256
    # Hot-row cache capacity in rows; 0 disables the cache (queries
    # always recompute).  Must be a multiple of cache_ways.
    cache_rows: int = 0
    cache_ways: int = 4
    # Serving-store storage precision (same vocabulary as HaloPrecision).
    storage: str = "fp32"
    # Aggregation backend + halo_spmm selection-ladder overrides for the
    # query-time neighbor reduction (see repro.kernels.spmm.ops).
    backend: str = "auto"
    resident_max_bytes: Optional[int] = None
    chunk_rows: Optional[int] = None

    def __post_init__(self):
        if self.storage not in PRECISIONS:
            raise ValueError(f"storage {self.storage!r} not in {PRECISIONS}")
        if self.batch_size < 1:
            raise ValueError(f"batch_size {self.batch_size} < 1")
        if self.cache_ways < 1:
            raise ValueError(f"cache_ways {self.cache_ways} < 1")
        if self.cache_rows < 0 or self.cache_rows % self.cache_ways:
            raise ValueError(
                f"cache_rows {self.cache_rows} must be a non-negative "
                f"multiple of cache_ways {self.cache_ways}")

    @property
    def cache_sets(self) -> int:
        return self.cache_rows // self.cache_ways

    @property
    def precision(self) -> HaloPrecision:
        return HaloPrecision(self.storage)


# ---------------------------------------------------------------------------
# Host-side plan: slot layout, routing, query ELL
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServePlan:
    """Host-side serving layout/routing (numpy; build once per graph).

    ``query_data()`` / ``refresh_data()`` / ``sharded_data(data)`` bundle
    the traced-array views each jitted entry point takes.
    """

    num_nodes: int
    num_parts: int
    part_rows: int            # S — padded local rows per part
    serve_rows: int           # S + 1 (per-shard sentinel row included)
    store_rows: int           # R = M · (S + 1)
    halo_size: int            # H — per-part out-of-part slots
    serve_map: np.ndarray     # (N+1,) global id → serve slot (sentinel R-1)
    local_ids: np.ndarray     # (M, S) global id of each local row
    local_valid: np.ndarray   # (M, S) bool
    local_slots: np.ndarray   # (M, S) serve slot of each local row
    sentinel_slots: np.ndarray  # (M,) per-shard sentinel slots
    halo_slots: np.ndarray    # (M, H) serve slot of each halo entry
    pull: PullPlan            # serving-layout collective-pull routing
    nbr: np.ndarray           # (N+1, Din) in-neighbor global ids, sentinel N
    wts: np.ndarray           # (N+1, Din) in-edge weights

    def query_data(self) -> dict:
        """Traced arrays of :func:`serve_query` (the ``qdata`` dict)."""
        return {"serve_map": jnp.asarray(self.serve_map),
                "nbr": jnp.asarray(self.nbr),
                "wts": jnp.asarray(self.wts)}

    def refresh_data(self) -> dict:
        """Traced arrays of the refresh step (the ``rdata`` dict)."""
        return {"local_ids": jnp.asarray(self.local_ids),
                "local_valid": jnp.asarray(self.local_valid),
                "local_slots": jnp.asarray(self.local_slots),
                "sentinel_slots": jnp.asarray(self.sentinel_slots)}

    def sharded_data(self, data: dict) -> dict:
        """Traced arrays of :func:`serve_query_sharded`: the serving
        PullPlan routing plus the per-part training ELL (the out-ELL
        addresses the pulled slab by halo position, which is exactly
        where the serving plan's ``recv_positions`` land each row)."""
        struct = data["struct"]
        return {"send": jnp.asarray(self.pull.send_offsets),
                "recv": jnp.asarray(self.pull.recv_positions),
                "in_nbr": struct["in_nbr"], "in_wts": struct["in_wts"],
                "out_nbr": struct["out_nbr"], "out_wts": struct["out_wts"]}


def build_serve_plan(data: dict) -> ServePlan:
    """Derive the serving layout from a ``prepare_graph_data`` dict.

    Needs the host-side ``_sp`` metadata (the partition build) and the
    full M=1 view; the serving slot space is the all-node owner-sharded
    layout described in the module docstring.
    """
    sp = data.get("_sp")
    if sp is None:
        raise ValueError("build_serve_plan needs prepare_graph_data's "
                         "host-side '_sp' metadata (don't strip it "
                         "before building the plan)")
    local_ids = np.asarray(sp.local_ids)
    local_valid = np.asarray(sp.local_valid)
    M, S = local_ids.shape
    srows = S + 1
    R = M * srows
    n = int(sp.num_nodes)

    serve_map = np.full(n + 1, R - 1, np.int32)
    for m in range(M):
        v = local_valid[m]
        serve_map[local_ids[m][v]] = m * srows + np.where(v)[0]
    local_slots = (np.arange(M, dtype=np.int32)[:, None] * srows
                   + np.arange(S, dtype=np.int32)[None, :])
    sentinel_slots = (np.arange(M, dtype=np.int32) + 1) * srows - 1

    halo_ids = np.asarray(sp.halo_ids)
    halo_valid = np.asarray(sp.halo_valid)
    halo_slots = np.where(halo_valid,
                          serve_map[np.minimum(halo_ids, n)],
                          R - 1).astype(np.int32)
    pull = build_pull_plan(halo_slots, halo_valid, sp.halo_size, srows)

    # Full-view in-ELL re-keyed to (n+1) global-id rows: row v lists v's
    # in-neighbors (full view local index == global id by construction),
    # row n is the all-sentinel padding row queries clamp into.
    full_nbr = np.asarray(data["full_struct"]["in_nbr"])[0]
    full_wts = np.asarray(data["full_struct"]["in_wts"])[0]
    full_ids = np.asarray(data["full_ids"])[0]
    if not np.array_equal(full_ids[:n], np.arange(n)):
        raise ValueError("full view rows are not in ascending global-id "
                         "order; the serving query ELL cannot be "
                         "re-keyed by node id")
    din = full_nbr.shape[1]
    nbr = np.full((n + 1, din), n, np.int32)
    wts = np.zeros((n + 1, din), np.float32)
    nbr[:n] = np.where(full_nbr[:n] >= n, n, full_nbr[:n])
    wts[:n] = full_wts[:n]

    return ServePlan(num_nodes=n, num_parts=M, part_rows=S,
                     serve_rows=srows, store_rows=R,
                     halo_size=int(sp.halo_size), serve_map=serve_map,
                     local_ids=local_ids, local_valid=local_valid,
                     local_slots=local_slots.astype(np.int32),
                     sentinel_slots=sentinel_slots,
                     halo_slots=halo_slots, pull=pull, nbr=nbr, wts=wts)


# ---------------------------------------------------------------------------
# Serving store: init + donation-friendly refresh
# ---------------------------------------------------------------------------

def init_serve_store(plan: ServePlan, hidden: int,
                     precision: HaloPrecision = HaloPrecision()) -> dict:
    """All-node single-layer serving slab + the version scalar:
    {"data": (1, R, hidden)[, "scale"], "version": int32 ()}."""
    store = halo_exchange.init_store(1, plan.store_rows - 1, hidden,
                                     precision)
    store["version"] = jnp.zeros((), jnp.int32)
    return store


def store_bare(store: dict) -> dict:
    """The HaloExchange view of a serving store (version leaf stripped —
    pull/push paths iterate exactly {"data"[, "scale"]})."""
    return {k: store[k] for k in ("data", "scale") if k in store}


def make_refresh_fn(mesh=None, serve_rows: int = None, donate: bool = True):
    """Jitted in-place serving-store refresh.

    Returns ``refresh(store, reps_top, rdata) -> store`` where
    ``reps_top`` is the (N_pad, hidden) top-layer input table
    (:func:`repro.core.digest.top_layer_reps`) and ``rdata`` is
    ``ServePlan.refresh_data()``.  The store argument is **donated**: the
    scatter reuses the old slab's buffers, so a serving deployment holds
    one store-sized allocation across refreshes.  Every refresh bumps
    ``version``, invalidating all hot-row cache entries at once.

    With ``mesh`` the scatter goes through the shard-local
    :func:`halo_exchange.shard_push` (pass ``serve_rows`` =
    ``ServePlan.serve_rows``); otherwise the SPMD
    :func:`halo_exchange.push` fallback.
    """
    if mesh is not None and serve_rows is None:
        raise ValueError("mesh refresh needs serve_rows "
                         "(ServePlan.serve_rows)")

    def _refresh(store, reps_top, rdata):
        ids = jnp.minimum(rdata["local_ids"], reps_top.shape[0] - 1)
        reps = reps_top[ids][:, None]                   # (M, 1, S, hidden)
        bare = store_bare(store)
        if mesh is None:
            new = halo_exchange.push(bare, rdata["local_slots"],
                                     rdata["local_valid"], reps,
                                     rdata["sentinel_slots"])
        else:
            new = halo_exchange.shard_push(bare, rdata["local_slots"],
                                           rdata["local_valid"], reps,
                                           serve_rows, mesh)
        new["version"] = store["version"] + 1
        return new

    return jax.jit(_refresh, donate_argnums=(0,) if donate else ())


def refresh_or_degrade(refresh_fn, store, reps_top, rdata,
                       stats: dict = None) -> tuple[dict, dict]:
    """Deploy a refresh; on ANY failure keep serving the old store.

    The degraded-mode contract: a refresh that raises mid-deployment
    (bad reps shape, placement error, an upstream trainer handing over
    garbage) must not take serving down — the previous store version
    keeps answering queries bitwise-identically, and because the
    version scalar was never bumped, every hot-row cache entry remains
    valid (the version-compare cache needs no special casing; pinned
    by tests/test_serving.py).  The failure is *counted*, not hidden:
    ``stats["degraded_refreshes"]`` increments so operators can alarm
    on a store that has silently stopped updating.

    Pair with ``make_refresh_fn(donate=False)`` when degradation
    matters: a donated store argument may have its buffers consumed by
    the very call that fails, leaving nothing to keep serving from.

    Returns ``(store, stats)`` — the new store on success, the old one
    on failure; ``stats`` gains ``refreshes``/``degraded_refreshes``
    counts (a fresh dict when None is passed).
    """
    stats = dict(stats) if stats else {"refreshes": 0,
                                       "degraded_refreshes": 0}
    try:
        new = refresh_fn(store, reps_top, rdata)
        jax.block_until_ready(new)
    except Exception:
        stats["degraded_refreshes"] += 1
        return store, stats
    stats["refreshes"] += 1
    return new, stats


# ---------------------------------------------------------------------------
# Hot-row cache
# ---------------------------------------------------------------------------

def init_cache(scfg: ServeConfig, width: int) -> dict:
    """Empty hot-row cache pytree for rows of ``width`` (= num_classes).

    tags/vers are -1 (no slot, no version — never matches), so a fresh
    cache misses everything; ``last`` is the LRU clock (per-way last
    access step), ``step`` the batch counter, hits/misses the counters
    the benchmark reads.  ``cache_rows == 0`` keeps only the counters.
    """
    counters = {"hits": jnp.zeros((), jnp.int32),
                "misses": jnp.zeros((), jnp.int32)}
    if scfg.cache_rows == 0:
        return counters
    sets, ways = scfg.cache_sets, scfg.cache_ways
    return {"tags": jnp.full((sets, ways), -1, jnp.int32),
            "vers": jnp.full((sets, ways), -1, jnp.int32),
            "last": jnp.zeros((sets, ways), jnp.int32),
            "rows": jnp.zeros((sets, ways, width), jnp.float32),
            "step": jnp.zeros((), jnp.int32), **counters}


def hit_rate(cache: dict) -> float:
    """hits / (hits + misses) over every valid query served so far."""
    h, m = int(cache["hits"]), int(cache["misses"])
    return h / max(h + m, 1)


def _cache_lookup(cache, slots, version):
    """Vectorized set-associative probe: returns (hit, rows, line, way)."""
    sets = cache["tags"].shape[0]
    line = slots % sets                                     # (B,)
    hit_w = ((cache["tags"][line] == slots[:, None])
             & (cache["vers"][line] == version))            # (B, ways)
    hit = jnp.any(hit_w, axis=1)
    way = jnp.argmax(hit_w, axis=1)
    return hit, cache["rows"][line, way], line, way


def _cache_commit(cache, slots, version, fresh_rows, hit, line, way, valid):
    """Touch LRU on hits, fill at most one victim way per set from the
    missed rows, and advance the counters — one deterministic scatter.

    Among a set's misses the *highest batch index* wins (scatter-max over
    batch positions), and all of a winner's writes (tag, version, clock,
    data) go to the same (line, way) — losers are redirected to a padded
    dummy set row that is sliced off, so a duplicate-slot batch can never
    interleave one row's tag with another row's data.
    """
    sets = cache["tags"].shape[0]
    b = slots.shape[0]
    step2 = cache["step"] + 1
    touched = cache["last"].at[line, way].max(
        jnp.where(hit & valid, step2, 0))
    # Victim way per probe: any dead way first (empty tag or stale
    # version — both unreadable), else least-recently-used.
    dead = (cache["vers"][line] != version) | (cache["tags"][line] < 0)
    evict_way = jnp.argmin(jnp.where(dead, -1, touched[line]), axis=1)
    want = (~hit) & valid
    cand = jnp.where(want, jnp.arange(b, dtype=jnp.int32), -1)
    winner = jnp.full((sets,), -1, jnp.int32).at[line].max(cand)
    do = want & (winner[line] == jnp.arange(b, dtype=jnp.int32))
    wline = jnp.where(do, line, sets)           # losers → dummy set row

    def pad1(a):
        return jnp.pad(a, ((0, 1),) + ((0, 0),) * (a.ndim - 1))

    return {
        "tags": pad1(cache["tags"]).at[wline, evict_way].set(slots)[:sets],
        "vers": pad1(cache["vers"]).at[wline, evict_way]
                .set(version)[:sets],
        "last": pad1(touched).at[wline, evict_way].set(step2)[:sets],
        "rows": pad1(cache["rows"]).at[wline, evict_way]
                .set(fresh_rows)[:sets],
        "step": step2,
        "hits": cache["hits"] + jnp.sum((hit & valid).astype(jnp.int32)),
        "misses": cache["misses"] + jnp.sum(want.astype(jnp.int32)),
    }


# ---------------------------------------------------------------------------
# The top-layer math over a query batch (shared by both engines)
# ---------------------------------------------------------------------------

def _side_spmm(scfg: ServeConfig, side: dict, wts) -> jax.Array:
    """One aggregation side through the halo_spmm selection ladder."""
    return halo_spmm(side["nbr"], wts, side["data"], side.get("scale"),
                     backend=scfg.backend,
                     resident_max_bytes=scfg.resident_max_bytes,
                     chunk_rows=scfg.chunk_rows)


def _batch_top_layer(cfg, scfg: ServeConfig, p, h_self, sides):
    """Top GNN layer restricted to a query batch.

    ``sides`` are aggregation sides, each {"nbr": (B, D) row ids into its
    "data" slab, "wts": (B, D), "valid": (B, D), "data"[, "scale"]}: the
    fast path passes ONE side (the full-view ELL against the whole
    store, exactly the fused sum the full-graph forward computes — the
    gcn/sage bitwise-parity invariant), the SPMD engine two (the
    in-shard + pulled-halo split of the training epoch).  Mirrors the
    layer math of ``repro.models.gnn`` term for term.
    """
    if cfg.model == "gcn":
        agg = _side_spmm(scfg, sides[0], sides[0]["wts"])
        for s in sides[1:]:
            agg = agg + _side_spmm(scfg, s, s["wts"])
        return dense(agg, p["w"], p["b"])
    if cfg.model == "sage":
        denom = jnp.sum(sides[0]["wts"], axis=1, keepdims=True)
        for s in sides[1:]:
            denom = denom + jnp.sum(s["wts"], axis=1, keepdims=True)
        denom = jnp.maximum(denom, 1e-12)
        agg = _side_spmm(scfg, sides[0], sides[0]["wts"] / denom)
        for s in sides[1:]:
            agg = agg + _side_spmm(scfg, s, s["wts"] / denom)
        return (dense(h_self, p["w_self"]) + dense(agg, p["w_nbr"])
                + p["b"])
    if cfg.model != "gat":
        raise ValueError(cfg.model)

    z_self = jnp.einsum("bd,dhk->bhk", h_self, p["w"])
    s_dst = jnp.einsum("bhk,hk->bh", z_self, p["a_dst"])
    scored = []
    for s in sides:
        rows = halo_gather(s["nbr"], s["data"], s.get("scale"))
        z = jnp.einsum("bkd,dhj->bkhj", rows, p["w"])       # (B, D, h, j)
        e = jax.nn.leaky_relu(
            s_dst[:, None, :] + jnp.einsum("bkhj,hj->bkh", z, p["a_src"]),
            0.2)
        v = s["valid"][..., None]
        scored.append((z, jnp.where(v, e, -1e30), v))
    m = scored[0][1].max(axis=1)
    for _, e, _ in scored[1:]:
        m = jnp.maximum(m, e.max(axis=1))
    m = jax.lax.stop_gradient(m)                            # (B, heads)
    probs = [jnp.exp(e - m[:, None, :]) * v for _, e, v in scored]
    denom = jnp.sum(probs[0], axis=1)
    for pe in probs[1:]:
        denom = denom + jnp.sum(pe, axis=1)
    denom = denom + 1e-16
    out = 0.0
    for (z, _, _), pe in zip(scored, probs):
        out = out + jnp.einsum("bkh,bkhj->bhj", pe / denom[:, None, :], z)
    return out.reshape(out.shape[0], -1) + p["b"]


# ---------------------------------------------------------------------------
# Query engines
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "scfg"))
def serve_query(cfg, scfg: ServeConfig, params, store, cache, qdata,
                q) -> tuple[jax.Array, dict]:
    """Batched prediction query against the serving store (fast path).

    q: (batch_size,) global node ids; pad short batches with
    ``num_nodes`` (padding rows are excluded from the cache counters and
    return the sentinel-row logits).  Returns (logits (B, classes),
    new_cache).  ``cfg``/``scfg`` are static jit-cache keys.
    """
    n = qdata["serve_map"].shape[0] - 1
    if q.shape != (scfg.batch_size,):
        raise ValueError(
            f"query batch shape {q.shape} != (batch_size={scfg.batch_size},)"
            " — pad with the sentinel id num_nodes (ServeConfig.batch_size"
            " is a static jit-cache key, not a bound)")
    valid = q < n
    qc = jnp.minimum(q, n)
    slots = qdata["serve_map"][qc]

    data, scale = halo_exchange.layer_table(store_bare(store), 0)
    nbr_ids = qdata["nbr"][qc]                              # (B, Din)
    side = {"nbr": qdata["serve_map"][nbr_ids],
            "wts": qdata["wts"][qc],
            "valid": nbr_ids < n, "data": data}
    if scale is not None:
        side["scale"] = scale
    h_self = halo_gather(slots, data, scale)
    p = params[f"layer_{cfg.num_layers - 1}"]
    fresh = _batch_top_layer(cfg, scfg, p, h_self, [side])

    if scfg.cache_rows == 0:
        counters = dict(cache)
        counters["misses"] = (cache["misses"]
                              + jnp.sum(valid.astype(jnp.int32)))
        return fresh, counters
    hit, rows, line, way = _cache_lookup(cache, slots, store["version"])
    hit = hit & valid
    logits = jnp.where(hit[:, None], rows, fresh)
    new_cache = _cache_commit(cache, slots, store["version"], fresh, hit,
                              line, way, valid)
    return logits, new_cache


@functools.partial(jax.jit,
                   static_argnames=("cfg", "scfg", "mesh", "halo_size"))
def serve_query_sharded(cfg, scfg: ServeConfig, mesh, halo_size: int,
                        params, store, sdata, q_rows) -> jax.Array:
    """SPMD batched query over the mesh-sharded serving store.

    q_rows: (M, B) part-local row indices (use ``part_rows`` as padding).
    Out-of-shard halo rows arrive through ``collective_pull`` with the
    serving PullPlan — the ragged all_to_all, zero all-gathers — while
    in-shard rows are read from the device's own slab re-view; the top
    layer runs per part, each device its own parts
    (:func:`halo_exchange.per_subgraph`), in the training epoch's split
    (in + out) aggregation form.  Returns (M, B, classes) logits.
    """
    slab = halo_exchange.collective_pull(store_bare(store), sdata["send"],
                                         sdata["recv"], halo_size, mesh)
    m_parts, s_rows = sdata["in_nbr"].shape[:2]
    srows = s_rows + 1
    hidden = store["data"].shape[-1]
    loc = store["data"][0].reshape(m_parts, srows, hidden)
    loc_scale = (store["scale"][0].reshape(m_parts, srows, 1)
                 if "scale" in store else None)

    qc = jnp.minimum(q_rows, s_rows - 1)                    # (M, B)
    take = jax.vmap(lambda a, i: a[i])
    in_nbr = take(sdata["in_nbr"], qc)
    out_nbr = take(sdata["out_nbr"], qc)
    side_in = {"nbr": in_nbr, "wts": take(sdata["in_wts"], qc),
               "valid": in_nbr < s_rows, "data": loc}
    side_out = {"nbr": out_nbr, "wts": take(sdata["out_wts"], qc),
                "valid": out_nbr < halo_size, "data": slab["data"][:, 0]}
    if loc_scale is not None:
        side_in["scale"] = loc_scale
        side_out["scale"] = slab["scale"][:, 0]
        h_self = jax.vmap(halo_gather)(qc, loc, loc_scale)
    else:
        h_self = jax.vmap(lambda i, d: halo_gather(i, d))(qc, loc)

    p = params[f"layer_{cfg.num_layers - 1}"]
    return halo_exchange.per_subgraph(
        lambda p, hs, si, so: _batch_top_layer(cfg, scfg, p, hs, [si, so]),
        mesh, in_axes=(None, 0, 0, 0), what="sharded query",
        check_vma=vma_checkable(scfg.backend))(p, h_self, side_in, side_out)


def serve_shardings(store: dict, sdata: dict, mesh, axis: str = "data"):
    """(store, sdata, q_rows) NamedShardings for the SPMD query step:
    store slot-sharded over the exchange axes (version replicated), the
    PullPlan tables by their leading owner/requester axis, per-part
    arrays by the part axis, params replicated by the caller."""
    axes = halo_exchange.exchange_axes(mesh, axis)
    mdim = axes if len(axes) > 1 else axes[0]
    rep = NamedSharding(mesh, P())
    slot = NamedSharding(mesh, P(None, mdim, None))
    store_sh = {"data": slot, "version": rep}
    if "scale" in store:
        store_sh["scale"] = slot
    plan_sh = NamedSharding(mesh, P(mdim, None, None))
    m_sh = NamedSharding(mesh, P(mdim))
    sdata_sh = {k: (plan_sh if k in ("send", "recv") else m_sh)
                for k in sdata}
    return store_sh, sdata_sh, NamedSharding(mesh, P(mdim, None))


# ---------------------------------------------------------------------------
# Workload synthesis (host-side)
# ---------------------------------------------------------------------------

def zipf_queries(num_nodes: int, batch_size: int, num_batches: int,
                 skew: float = 1.1, *, seed: int = 0,
                 hot_ids: Optional[np.ndarray] = None) -> np.ndarray:
    """(num_batches, batch_size) int32 Zipf(``skew``) query stream.

    Rank r is drawn with probability ∝ r^-skew; ``hot_ids`` optionally
    maps popularity rank → node id (e.g. nodes sorted by descending
    degree, so hubs are hottest — the realistic correlation for social /
    recommendation traffic).  Identity by default.
    """
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, num_nodes + 1, dtype=np.float64)
    prob = ranks ** -float(skew)
    prob /= prob.sum()
    draws = rng.choice(num_nodes, size=(num_batches, batch_size), p=prob)
    if hot_ids is not None:
        draws = np.asarray(hot_ids, np.int64)[draws]
    return draws.astype(np.int32)
