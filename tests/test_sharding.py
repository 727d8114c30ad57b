"""Logical axis rules + an 8-device lowering test (subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (DEFAULT_RULES, spec_for_axes)
from repro.launch.mesh import make_host_mesh


def test_resolve_basic():
    mesh = make_host_mesh(1, 1)
    spec = spec_for_axes(("batch", "seq", "embed"), mesh)
    assert isinstance(spec, P)


def test_divisibility_guard():
    """56 heads on a 16-way model axis must fall back to replicated."""
    mesh = make_host_mesh(1, 1)   # 1 device, but rules logic is size-aware
    # emulate a 16-way axis by checking the resolver's math directly
    from repro.distributed.sharding import _resolve
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    spec = _resolve(("embed", "heads", "head_dim"), DEFAULT_RULES,
                    FakeMesh(), shape=(7168, 56, 128))
    assert spec[1] is None            # 56 % 16 != 0 → dropped
    assert spec[0] is None or spec[0] == "data"  # embed: no fsdp by default
    spec2 = _resolve(("embed", "heads", "head_dim"), DEFAULT_RULES,
                     FakeMesh(), shape=(7168, 64, 128))
    assert spec2[1] == "model"        # 64 % 16 == 0 → sharded


def test_no_double_axis_use():
    from repro.distributed.sharding import _resolve
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    rules = dict(DEFAULT_RULES, embed="model")
    spec = _resolve(("embed", "mlp"), rules, FakeMesh(),
                    shape=(4096, 16384))
    # "model" must be used only once across dims
    assert [s for s in spec].count("model") <= 1


SUBPROCESS_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, json
    from repro.configs import get_smoke_arch
    from repro.distributed.sharding import axis_rules, shardings_for_specs
    from repro.launch.mesh import make_host_mesh
    from repro.launch.specs import (abstract_from_specs, input_specs,
                                    train_state_specs, batch_logical_axes)
    from repro.nn.params import ParamSpec
    from repro.train import TrainSettings, make_train_step
    import dataclasses

    cfg = dataclasses.replace(get_smoke_arch("qwen3-0.6b"),
                              num_heads=4, num_kv_heads=2)
    mesh = make_host_mesh(data=2, model=2, pod=2)
    settings = TrainSettings(sync_mode="digest", n_pod=2, sync_interval=5)
    step = make_train_step(cfg, settings)
    with axis_rules(mesh, {"embed": "data"}):
        ss = train_state_specs(cfg, n_pod=2, digest_pods=True)
        state_abs = abstract_from_specs(ss)
        state_sh = shardings_for_specs(ss, mesh, {"embed": "data"})
        batch_abs = {
            "tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32),
            "mask": jax.ShapeDtypeStruct((8, 16), jnp.float32)}
        batch_sh = {k: shardings_for_specs(
            ParamSpec(tuple(v.shape), ("batch", "seq"), dtype=v.dtype),
            mesh, {}) for k, v in batch_abs.items()}
        lowered = jax.jit(step, in_shardings=(state_sh, batch_sh)).lower(
            state_abs, batch_abs)
        compiled = lowered.compile()
        from repro.launch.dryrun import cost_properties
        cost = cost_properties(compiled)
        print(json.dumps({"ok": True, "flops": cost.get("flops", 0)}))
""")


def test_multi_device_lowering_subprocess():
    """Real 8-device (2 pod x 2 data x 2 model) lowering of the DIGEST
    train step — proves shardings are coherent end to end."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_TEST], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["ok"] and payload["flops"] > 0


PER_SUBGRAPH_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.halo_exchange import on_every_device, per_subgraph
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=4)
    w = jnp.asarray([0.5, -2.0, 3.0])
    x = jnp.arange(24.0).reshape(8, 3) / 7.0

    def loss(w, x):
        return jnp.sum(jnp.sin(w * x))

    vg = jax.value_and_grad(loss)
    want = jax.vmap(vg, in_axes=(None, 0))(w, x)
    for check in (True, False):
        got = jax.jit(per_subgraph(vg, mesh, (None, 0),
                                   check_vma=check))(w, x)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-6)
        whole = jax.grad(lambda w: jnp.sum(on_every_device(
            lambda w, x: jnp.sin(w * x), mesh, check_vma=check)(w, x)))
        np.testing.assert_allclose(jax.jit(whole)(w),
                                   jax.grad(lambda w: jnp.sum(
                                       jnp.sin(w * x)))(w), rtol=1e-6)
    print("PER_SUBGRAPH_OK")
""")


def test_per_subgraph_gradients_subprocess():
    """On a 4-device mesh, a gradient taken per subgraph inside
    ``per_subgraph`` is that subgraph's own (not summed over devices),
    and a gradient through ``on_every_device`` is the single-device one,
    with the varying-axes check on and off."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", PER_SUBGRAPH_TEST], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PER_SUBGRAPH_OK" in out.stdout
