"""Compiled-HLO assertion harness for the DIGEST epoch.

Lowers the *jitted* epoch function on a forced multi-device mesh with the
production shardings (``repro.launch.train_gnn.subgraph_shardings``) and
exposes the compiled module's collective-op census, so tests can assert
communication invariants on the program XLA actually emits instead of
spot-checking trajectories.

The key fact the assertions lean on: after SPMD partitioning, every HLO
op is device-local — **all** cross-device data movement is explicit
collective ops (all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute).  "The push never crosses devices" is therefore
exactly the statement "the only collectives in the module are the
expected ragged all-to-all pulls plus the (L-1)-or-scalar-sized metric /
gradient all-reduces" — zero all-gathers, zero collective-permutes.
"""
from __future__ import annotations

import os
import subprocess
import sys

import jax

# One grammar for collective-op matching and group parsing, shared with
# the dry-run's inter-pod byte split (see repro.launch.hlo_census) —
# the two censuses must never disagree about what counts as an op.
from repro.launch.hlo_census import (COLLECTIVES, match_collective,
                                     op_groups)


# Single-device vs SPMD-partitioned epochs: the partitioner re-tiles and
# re-fuses the forward's reductions, so pushed representations agree to a
# few float32 ULPs (of the array's largest magnitude), not bitwise; the
# routing itself is exact (the KVS parity checks compare it bitwise).
PARTITIONED_ULPS = 8


def tree_close(a: dict, b: dict, what: str = ""):
    """``a`` equals ``b`` up to the partitioner's reassociation: floats
    within ``PARTITIONED_ULPS`` ULPs of ``max|b|``, int8 codes within one
    quantization step (a reassociated row may round the other way)."""
    import numpy as np

    assert sorted(a) == sorted(b)
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        if np.issubdtype(y.dtype, np.integer):
            diff = np.abs(x.astype(np.int32) - y.astype(np.int32))
            assert diff.max(initial=0) <= 1, f"{what}[{k}]"
            continue
        x, y = x.astype(np.float32), y.astype(np.float32)
        tol = (PARTITIONED_ULPS * np.finfo(np.float32).eps
               * float(np.abs(y).max(initial=0)))
        np.testing.assert_allclose(x, y, rtol=0, atol=tol,
                                   err_msg=f"{what}[{k}]")


def run_forced_device_subprocess(test_file: str, marker: str,
                                 devices: int = 8, timeout: int = 900):
    """Re-launch ``test_file`` as ``__main__`` with a forced N-device CPU
    platform, so multi-device checks run even on single-device hosts
    (the in-process pytest variants cover the CI forced-device jobs).
    Asserts a clean exit and that ``marker`` was printed."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(test_file)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(repo, "src") + os.pathsep
                         + os.path.join(repo, "tests") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, os.path.abspath(test_file)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\n" \
                                f"stderr:\n{res.stderr}"
    assert marker in res.stdout, res.stdout


def collective_counts(hlo_text: str) -> dict:
    """Count each collective op in a compiled HLO module's text.

    Async pairs (``-start``/``-done``) are counted once, at ``-start``.
    """
    counts = {c: 0 for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        op = match_collective(line)
        if op is not None:
            counts[op] += 1
    return counts


def collective_axis_census(hlo_text: str, mesh) -> dict:
    """Per-mesh-axis collective census of a compiled HLO module.

    Returns ``{op: {axes_tuple: count}}`` where ``axes_tuple`` is the
    (mesh-ordered) tuple of axis names whose coordinate *varies* inside
    the op's replica groups — e.g. on the ("pod", "data", "model") mesh
    an intra-pod all-to-all shows up as ``("data",)``, the inter-pod
    permute as ``("pod",)`` and a global gradient all-reduce as the
    full axis tuple.  Ops whose groups cannot be parsed (or that carry
    no groups) are filed under ``None`` so they are never silently
    dropped.  This is what lets tests assert not just *how many*
    collectives the two-stage exchange emits but *which links they
    ride* — the inter-pod hop must never widen to the combined axes.

    Group parsing (explicit / iota ``replica_groups``, permute
    ``source_target_pairs``) is shared with the dry-run's inter-pod
    byte split via ``repro.launch.hlo_census.op_groups`` — one grammar,
    two consumers that must agree.
    """
    import numpy as np

    # HLO group entries are LOGICAL device numbers — positions in the
    # flattened device assignment (mesh.devices C order) — not hardware
    # device ids; the two coincide on forced-CPU host meshes but not on
    # a real TPU mesh (make_mesh reorders devices for ICI topology).
    shape = np.asarray(mesh.devices).shape
    names = mesh.axis_names

    def classify(groups):
        varying = set()
        for grp in groups:
            cs = [np.unravel_index(i, shape) for i in grp]
            for d, name in enumerate(names):
                if len({c[d] for c in cs}) > 1:
                    varying.add(name)
        return tuple(a for a in names if a in varying)

    census = {c: {} for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        op = match_collective(line)
        if op is not None:
            groups = op_groups(line.strip())
            key = classify(groups) if groups is not None else None
            census[op][key] = census[op].get(key, 0) + 1
    return census


def expected_collective_permute(storage: str, pods: int,
                                model: str = "gcn",
                                num_layers: int = None) -> int:
    """collective-permute count of one *multi-pod* collective PULL: the
    inter-pod stage ships each pulled tensor through ``pods - 1``
    shifted ppermute rounds (one permute per tensor on the 2-pod
    production mesh); tensor count is the same per-storage/per-model
    arithmetic as :func:`expected_all_to_all`.  Zero on a single-pod
    mesh — the exchange collapses to the intra-pod all_to_all alone."""
    return (pods - 1) * expected_all_to_all(storage, model, num_layers)


def expected_all_to_all(storage: str, model: str = "gcn",
                        num_layers: int = None,
                        predictor: bool = False) -> int:
    """all-to-all count of one collective PULL.

    gcn/sage pull the raw store: one op per store tensor ({data} or
    {data, scale}), the (L-1)-layer axis batched inside the exchange
    buffer — independent of depth.  gat (projected-row pull) exchanges
    one z tensor per hidden layer (widths differ per layer, so layers
    cannot batch into one buffer): (L-1) ops, ×2 with int8 scales.

    With the SAT ``predictor`` the pstore mirrors the store's tensors
    and rides the same routing — one extra op per pstore tensor on the
    raw-store pull; ZERO extra under the GAT dedup, whose prediction is
    folded shard-locally before projection (the pulled z tensors are
    unchanged)."""
    per_tensor = 2 if storage == "int8" else 1
    if model != "gat":
        return per_tensor * (2 if predictor else 1)
    if num_layers is None:
        num_layers = 2                    # make_epoch's gat default
    return per_tensor * (num_layers - 1)


def make_epoch(g, num_parts: int, mesh=None, *, storage: str = "fp32",
               pull_mode: str = "collective", model: str = "gcn",
               hidden: int = 32, sync_interval: int = 2,
               error_feedback: bool = False, fault_state: bool = False,
               max_staleness: int = None, predictor=None):
    """Build (jitted_epoch_fn, state, tdata) for graph ``g``.

    With ``mesh`` the epoch is jitted with the production shardings
    (store slot-sharded, (M, ...) arrays over "data"); without it the
    plain single-device program is returned.  ``fault_state`` attaches
    the fault-injection leaves (``push_ok`` / ``last_push_round``) so
    the fault-aware program's census can be compared to the plain one.
    """
    from repro.core import (PredictorConfig, TrainSettings,
                            attach_fault_state, init_state,
                            make_epoch_fn, prepare_graph_data)
    from repro.core.halo_exchange import HaloPrecision
    from repro.launch.train_gnn import subgraph_shardings
    from repro.models.gnn import GNNConfig
    from repro.optim import adam

    data = prepare_graph_data(g, num_parts)
    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    cfg = GNNConfig(model=model, num_layers=3 if model != "gat" else 2,
                    in_dim=g.features.shape[1], hidden_dim=hidden,
                    num_classes=int(g.labels.max()) + 1, heads=2)
    opt = adam(5e-3)
    pcfg = predictor or PredictorConfig()
    settings = TrainSettings(
        sync_interval=sync_interval, mode="digest", pull_mode=pull_mode,
        precision=HaloPrecision(storage, error_feedback=error_feedback),
        max_staleness=max_staleness, predictor=pcfg)
    state = init_state(cfg, opt, data, precision=settings.precision,
                       predictor=pcfg)
    if fault_state:
        state = attach_fault_state(state, num_parts)
    if mesh is None:
        fn = jax.jit(make_epoch_fn(cfg, opt, settings))
    else:
        data_sh, state_sh = subgraph_shardings(tdata, state, mesh)
        fn = jax.jit(make_epoch_fn(cfg, opt, settings, mesh=mesh),
                     in_shardings=(state_sh, data_sh))
    return fn, state, tdata


def compile_epoch(g, num_parts: int, mesh, **kw):
    """Lower + compile the sharded epoch; returns the Compiled object
    (``.as_text()`` is the partitioned per-device HLO module)."""
    fn, state, tdata = make_epoch(g, num_parts, mesh, **kw)
    return fn.lower(state, tdata).compile()


def make_sampled_epoch(g, num_parts: int, mesh=None, *,
                       storage: str = "fp32",
                       pull_mode: str = "collective", model: str = "gcn",
                       hidden: int = 32, sync_interval: int = 2,
                       fanout: int = 3, batch_seeds: int = 32,
                       estimator: str = "cv"):
    """Sampled-regime analogue of :func:`make_epoch`: build
    ``(jitted_step_fn, state, tdata, batch)`` where ``batch`` is the
    deterministic step-0 sampler draw (jnp-converted).  Same cfg /
    settings construction so census comparisons against ``make_epoch``
    are apples-to-apples."""
    import jax.numpy as jnp

    from repro.core import (TrainSettings, init_sampled_state,
                            make_sampled_epoch_fn, prepare_graph_data)
    from repro.core.halo_exchange import HaloPrecision
    from repro.graph import build_sampler
    from repro.launch.train_gnn import batch_shardings, subgraph_shardings
    from repro.models.gnn import GNNConfig
    from repro.optim import adam

    data = prepare_graph_data(g, num_parts)
    tdata = {k: v for k, v in data.items() if not k.startswith("_")}
    cfg = GNNConfig(model=model, num_layers=3 if model != "gat" else 2,
                    in_dim=g.features.shape[1], hidden_dim=hidden,
                    num_classes=int(g.labels.max()) + 1, heads=2)
    opt = adam(5e-3)
    settings = TrainSettings(
        sync_interval=sync_interval, mode="digest", pull_mode=pull_mode,
        precision=HaloPrecision(storage), sample_estimator=estimator)
    state = init_sampled_state(cfg, opt, data, precision=settings.precision)
    sampler = build_sampler(data, fanout, batch_seeds)
    batch = {k: jnp.asarray(v) for k, v in sampler.sample(0).items()}
    if mesh is None:
        fn = jax.jit(make_sampled_epoch_fn(cfg, opt, settings))
    else:
        data_sh, state_sh = subgraph_shardings(tdata, state, mesh)
        fn = jax.jit(make_sampled_epoch_fn(cfg, opt, settings, mesh=mesh),
                     in_shardings=(state_sh, data_sh,
                                   batch_shardings(mesh)))
    return fn, state, tdata, batch


def compile_sampled_epoch(g, num_parts: int, mesh, **kw):
    """Lower + compile the sharded sampled step (see
    :func:`compile_epoch`)."""
    fn, state, tdata, batch = make_sampled_epoch(g, num_parts, mesh, **kw)
    return fn.lower(state, tdata, batch).compile()
