"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination on the production mesh with ShapeDtypeStruct stand-ins (no
allocation), and extract the roofline terms.

MUST be run as its own process (`python -m repro.launch.dryrun ...`) — the
XLA_FLAGS assignment below executes before any jax import so jax sees 512
placeholder devices. Do NOT import this module from tests/benches.  It
pins the CPU platform itself, so on a machine with a TPU attached it
never takes the chip.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all --multi-pod both \
      --out results/dryrun.jsonl
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import re
import subprocess
import sys
import time
import traceback

import jax

from repro.configs import ALIASES, ARCH_IDS, get_arch
from repro.distributed.sharding import axis_rules, shardings_for_specs
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS,
                               make_production_mesh)
from repro.launch.specs import (SHAPES, abstract_from_specs,
                                batch_logical_axes, input_specs,
                                serve_state_specs, train_state_specs)
from repro.models.transformer import forward
from repro.train.trainer import (TrainSettings, make_serve_step,
                                 make_train_step)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
from repro.launch.hlo_census import COLLECTIVES as _COLLECTIVES
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def cost_properties(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty where XLA has none)."""
    return compiled.cost_analysis() or {}


def _groups_cross_pod(line: str, pod_boundary: int) -> bool:
    """True if any replica group / permute pair spans devices on both
    sides of ``pod_boundary`` — i.e. the collective rides the slow
    inter-pod link.  Group parsing (incl. collective-permute's
    ``source_target_pairs``: the two-stage halo exchange's inter-pod
    hop is exactly such an op, and it must show up in the inter-pod
    byte split) is shared with tests/hlo_utils via
    ``repro.launch.hlo_census``."""
    from repro.launch.hlo_census import groups_cross_boundary, op_groups

    groups = op_groups(line)
    return bool(groups) and groups_cross_boundary(groups, pod_boundary)


def collective_bytes(hlo_text: str, pod_boundary: int = 0) -> dict:
    """Sum result-shape bytes of every collective op in the HLO.

    ``pod_boundary`` > 0 additionally splits the total into intra-pod vs
    inter-pod bytes by replica-group analysis (devices [0, boundary) =
    pod 0)."""
    from repro.launch.hlo_census import match_collective

    totals = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    inter_pod = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # Shared op matching (-done lines skipped, counted at -start) —
        # the test census must agree line for line.
        op = match_collective(stripped)
        if op is None:
            continue
        lhs = stripped.split("=")[1] if "=" in stripped else stripped
        lhs = lhs.split(f" {op}")[0]
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(lhs):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        totals[op] += nbytes
        counts[op] += 1
        if pod_boundary and _groups_cross_pod(stripped, pod_boundary):
            inter_pod += nbytes
    totals_all = sum(totals.values())
    return {"per_op": totals, "counts": counts, "total": totals_all,
            "inter_pod": inter_pod}


def _rules_for(cfg, mesh, overrides: dict | None = None) -> dict:
    rules = {"embed": "data"}          # FSDP: shard big params over data
    rules.update(overrides or {})
    return rules


def _lower_case(cfg, shape_name: str, mesh, rules, sync_mode: str):
    sh = SHAPES[shape_name]
    kind = sh["kind"]
    n_pod = mesh.shape.get("pod", 1)
    with axis_rules(mesh, rules):
        batch_abs = input_specs(cfg, shape_name)
        b_axes = batch_logical_axes(cfg, shape_name)
        batch_sh = {
            k: shardings_for_specs(
                _axes_spec(v, b_axes[k]), mesh, rules)
            for k, v in batch_abs.items()}

        if kind == "train":
            digest = sync_mode == "digest" and n_pod > 1
            # NOTE: pod_impl="shard_map" (the cleaner production form)
            # trips an XLA SPMD-partitioner CHECK
            # (spmd_partitioner_util.cc:504 partition_group_list) on the
            # CPU backend at 512 devices — documented in EXPERIMENTS §Perf;
            # the vmap form lowers everywhere.
            settings = TrainSettings(
                sync_mode="digest" if digest else "every_step",
                n_pod=n_pod if digest else 1, sync_interval=10,
                pod_impl="vmap", total_steps=10_000)
            step_fn = make_train_step(cfg, settings)
            state_specs = train_state_specs(cfg, n_pod=settings.n_pod,
                                            digest_pods=digest)
            state_abs = abstract_from_specs(state_specs)
            state_sh = shardings_for_specs(state_specs, mesh, rules)
            # Donate the train state: params/opt buffers are reused for
            # the outputs (in-place update), as a real trainer would.
            jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abs, batch_abs)
        elif kind == "prefill":
            def prefill(params, batch):
                return forward(cfg, params, batch["tokens"],
                               batch.get("vision"))
            state_specs = serve_state_specs(cfg, shape_name)["params"]
            state_abs = abstract_from_specs(state_specs)
            state_sh = shardings_for_specs(state_specs, mesh, rules)
            jitted = jax.jit(prefill, in_shardings=(state_sh, batch_sh))
            lowered = jitted.lower(state_abs, batch_abs)
        else:
            long = kind == "decode_long"
            serve = make_serve_step(cfg, long=long)
            ss = serve_state_specs(cfg, shape_name)
            state_abs = abstract_from_specs(ss)
            state_sh = shardings_for_specs(ss, mesh, rules)
            jitted = jax.jit(
                lambda params, cache, batch:
                serve(params, cache, batch["tokens"]),
                in_shardings=(state_sh["params"], state_sh["cache"],
                              batch_sh))
            lowered = jitted.lower(state_abs["params"], state_abs["cache"],
                                   batch_abs)
    return lowered


def dryrun_case(arch: str, shape_name: str, multi_pod: bool,
                rules_override: dict | None = None,
                sync_mode: str = "digest",
                skip_unrolled: bool = False,
                cfg_overrides: dict | None = None) -> dict:
    """Lower + compile one (arch × shape × mesh) case, twice:

    * scanned layers → fast compile; ``memory_analysis`` (capacity / "does
      it fit" — the loop reuses buffers, so temp size is the real live set);
    * unrolled layers → true ``cost_analysis``/collective traffic (XLA
      counts while-loop bodies once, so the scanned HLO under-reports
      FLOPs/bytes/collectives by ~num_layers ×).
    """
    import dataclasses as _dc
    base = get_arch(arch)
    if cfg_overrides:
        base = _dc.replace(base, **cfg_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rules = _rules_for(base, mesh, rules_override)

    out = {
        "arch": base.name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips,
    }

    pod_boundary = (mesh.devices.size // mesh.shape["pod"]
                    if "pod" in mesh.axis_names else 0)

    # Pass 1: scanned — memory fit.
    t0 = time.perf_counter()
    cfg_scan = _dc.replace(base, scan_layers=True)
    compiled = _lower_case(cfg_scan, shape_name, mesh, rules,
                           sync_mode).compile()
    out["t_compile_scan_s"] = round(time.perf_counter() - t0, 2)
    mem = compiled.memory_analysis()
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes"):
            if hasattr(mem, attr):
                out[f"mem_{attr}"] = int(getattr(mem, attr))

    # Pass 2: unrolled — true per-device traffic for the roofline.
    if skip_unrolled:
        cost = cost_properties(compiled)
        coll = collective_bytes(compiled.as_text(), pod_boundary)
        scale = float(base.repeats)  # approximate loop-body rescale
        flops = float(cost.get("flops", 0.0)) * scale
        bytes_acc = float(cost.get("bytes accessed", 0.0)) * scale
        coll_total = coll["total"] * scale
        out["cost_basis"] = "scan_rescaled"
    else:
        t1 = time.perf_counter()
        cfg_unroll = _dc.replace(base, scan_layers=False)
        compiled_u = _lower_case(cfg_unroll, shape_name, mesh, rules,
                                 sync_mode).compile()
        out["t_compile_unroll_s"] = round(time.perf_counter() - t1, 2)
        cost = cost_properties(compiled_u)
        coll = collective_bytes(compiled_u.as_text(), pod_boundary)
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        coll_total = coll["total"]
        out["cost_basis"] = "unrolled"
        out["collective_per_op"] = coll["per_op"]
        out["collective_counts"] = coll["counts"]

    out.update({
        "hlo_flops": flops,
        "hlo_bytes": bytes_acc,
        "collective_bytes": coll_total,
        "inter_pod_bytes": coll.get("inter_pod", 0),
        # Roofline terms (seconds). cost_analysis and the HLO text are the
        # PER-DEVICE partitioned module (verified empirically: a matmul
        # sharded 8-ways reports 1/8 the FLOPs), so each term divides by a
        # single chip's peak, not by the fleet.
        "compute_term_s": flops / PEAK_FLOPS,
        "memory_term_s": bytes_acc / HBM_BW,
        "collective_term_s": coll_total / ICI_BW,
    })
    return out


def _axes_spec(sds, axes):
    from repro.nn.params import ParamSpec
    return ParamSpec(tuple(sds.shape), tuple(axes), init="zeros",
                     dtype=sds.dtype)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all",
                    choices=list(SHAPES) + ["all"])
    ap.add_argument("--multi-pod", dest="multi_pod", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--sync-mode", default="digest",
                    choices=["digest", "every_step"])
    ap.add_argument("--rules", default="{}",
                    help='JSON logical-rule overrides, e.g. {"embed":null}')
    ap.add_argument("--cfg", default="{}",
                    help='JSON ArchConfig overrides, e.g. '
                         '{"remat":false,"param_dtype":"bfloat16"}')
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--skip-unrolled", action="store_true",
                    help="skip the unrolled pass; rescale scan costs by "
                         "repeats (for compile-time-prohibitive cases)")
    ap.add_argument("--subprocess-each", action="store_true",
                    help="isolate every case in its own process")
    args = ap.parse_args()

    archs = ([ALIASES.get(args.arch, args.arch)] if args.arch != "all"
             else ARCH_IDS)
    shapes = [args.shape] if args.shape != "all" else list(SHAPES)
    pods = {"single": [False], "multi": [True],
            "both": [False, True]}[args.multi_pod]
    rules_override = json.loads(args.rules)
    cfg_overrides = json.loads(args.cfg)
    cfg_overrides = {k: (tuple(v) if isinstance(v, list) else v)
                     for k, v in cfg_overrides.items()}

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                if args.subprocess_each:
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape,
                           "--multi-pod", "multi" if mp else "single",
                           "--sync-mode", args.sync_mode,
                           "--rules", args.rules, "--cfg", args.cfg]
                    if args.out:
                        cmd += ["--out", args.out]
                    rc = subprocess.call(cmd)
                    failures += rc != 0
                    continue
                try:
                    res = dryrun_case(arch, shape, mp,
                                      rules_override=rules_override,
                                      sync_mode=args.sync_mode,
                                      skip_unrolled=args.skip_unrolled,
                                      cfg_overrides=cfg_overrides)
                    res["rules_override"] = rules_override
                    res["cfg_overrides"] = cfg_overrides
                    res["sync_mode"] = args.sync_mode
                    line = json.dumps(res)
                    print(line, flush=True)
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(line + "\n")
                except Exception:
                    failures += 1
                    print(f"FAILED {arch} {shape} multi_pod={mp}",
                          file=sys.stderr)
                    traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
