"""Graph recipes and their partition cache.

A recipe (``bench/graphs/<name>.json``) fixes the graph's structure from
its own ``structure_seed``, so every run of a cell has the same shapes
and hits the compile cache; ``--seed`` only draws the node features and
the weights (``inputs.py``).  The ``sbm`` generator is the program's
``repro.graph.generators.sbm_graph`` with the recipe's parameters; the
features it draws are replaced by the seed's.

Partitioning (the program's ``prepare_graph_data``) takes seconds, so its
arrays are cached under ``bench/.graph_cache``, keyed by the recipe, the
part count and a hash of the program sources that build them: a change
to the generator, the partitioner or the kernels' tile geometry
builds anew.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
CACHE_DIR = BENCH / ".graph_cache"
# Sources whose change alters the partitioned arrays.
HASHED_SOURCES = ("src/repro/graph", "src/repro/core", "src/repro/kernels/spmm")


def sbm(recipe: dict):
    """The recipe's graph from the program's own stochastic block model
    (``repro.graph.generators.sbm_graph``), with its undirected edges
    (u < v) for the reference; the features it draws are not used."""
    from repro.graph.generators import sbm_graph

    g = sbm_graph(num_nodes=recipe["num_nodes"],
                  num_classes=recipe["num_classes"],
                  avg_degree=recipe["avg_degree"],
                  p_in_out_ratio=recipe["p_in_out_ratio"],
                  feature_dim=recipe["feature_dim"],
                  noise=recipe["noise"], seed=recipe["structure_seed"],
                  frac=tuple(recipe["split"]), name=recipe["name"])
    src = np.repeat(np.arange(g.num_nodes), np.diff(g.indptr))
    keep = src < g.indices
    return g, np.stack([src[keep], g.indices[keep]], 1).astype(np.int32)


GENERATORS = {"sbm": sbm}


@dataclasses.dataclass
class GraphData:
    graph: object        # repro.graph.graph.Graph; features are zeros
    data: dict           # prepare_graph_data's arrays (numpy), no x_global
    edges: np.ndarray    # (E, 2) undirected edges of the recipe
    hit: bool            # loaded from the cache
    seconds: float


def cache_key(root: Path, recipe: dict, num_parts: int) -> str:
    h = hashlib.sha256(json.dumps([recipe, num_parts],
                                  sort_keys=True).encode())
    files = [Path(__file__)]
    for rel in HASHED_SOURCES:
        files += sorted(Path(root, rel).rglob("*.py"))
    for f in files:
        h.update(f.read_bytes())
    return h.hexdigest()[:16]


def _flatten(d: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out.update(_flatten(v, f"{prefix}{k}/"))
        else:
            out[prefix + k] = np.asarray(v)
    return out


def _unflatten(flat) -> dict:
    out: dict = {}
    for key in flat.files:
        node = out
        *path, leaf = key.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = flat[key]
    return out


def _build(recipe: dict, num_parts: int) -> dict:
    """Generate the recipe's graph and partition it with the program's
    ``prepare_graph_data``, on the host CPU (the chip's memory stays
    untouched, so a cache miss leaves ``peak_bytes_in_use`` as a hit
    does)."""
    import jax

    from repro.core import prepare_graph_data

    g, und = GENERATORS[recipe["generator"]](recipe)
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        data = prepare_graph_data(g, num_parts)
    wl = data["_worklist"]
    flat = _flatten({k: v for k, v in data.items()
                     if not k.startswith("_") and k != "x_global"})
    flat.update({f"_worklist/{f.name}": np.asarray(getattr(wl, f.name))
                 for f in dataclasses.fields(wl)})
    flat.update({"_graph/indptr": g.indptr, "_graph/indices": g.indices,
                 "_graph/labels": g.labels,
                 "_graph/train_mask": g.train_mask,
                 "_graph/val_mask": g.val_mask,
                 "_graph/test_mask": g.test_mask, "_graph/edges": und})
    return flat


def load(root: Path, recipe: dict, num_parts: int,
         cache_dir: Path = CACHE_DIR) -> GraphData:
    """The recipe's graph, partitioned into ``num_parts``: from the cache
    when its key matches, else built and written there."""
    from repro.graph.graph import Graph
    from repro.graph.partition import ChunkWorklist

    t = time.perf_counter()
    path = Path(cache_dir) / (f"{recipe['name']}-{num_parts}-"
                              f"{cache_key(root, recipe, num_parts)}.npz")
    hit = path.exists()
    if not hit:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".{os.getpid()}.tmp.npz")
        np.savez(tmp, **_build(recipe, num_parts))
        os.replace(tmp, path)
    with np.load(path) as flat:
        data = _unflatten(flat)
    gr = data.pop("_graph")
    wl = data.pop("_worklist")
    data["_worklist"] = ChunkWorklist(**{
        k: (v if v.ndim else v.item()) for k, v in wl.items()})
    n, d = recipe["num_nodes"], recipe["feature_dim"]
    graph = Graph(indptr=gr["indptr"], indices=gr["indices"],
                  features=np.broadcast_to(np.float32(0), (n, d)),
                  labels=gr["labels"], train_mask=gr["train_mask"],
                  val_mask=gr["val_mask"], test_mask=gr["test_mask"],
                  name=recipe["name"])
    return GraphData(graph=graph, data=data, edges=gr["edges"], hit=hit,
                     seconds=time.perf_counter() - t)
