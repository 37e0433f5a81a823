"""Sharding rules, compression, multi-device collectives (subprocess)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from _hypo import given, settings, st

from repro.config import get_model_config, get_parallel_config, list_archs
from repro.models import build_model
from repro.parallel.compression import (
    compress_with_feedback, dequantize_int8, quantize_int8,
)
from repro.parallel.sharding import ShardingRules


# ------------------------- sharding rules -------------------------

@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_specs_divisible(arch, multi_pod):
    """Every sharded dimension must divide by its mesh axis size for the
    FULL config on the production mesh — the invariant the dry-run needs."""
    model_cfg = get_model_config(arch)
    par = get_parallel_config(arch, multi_pod=multi_pod)
    model = build_model(model_cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    rules = ShardingRules(model_cfg, par)
    specs = rules.params_tree_specs(params)
    sizes = {"pod": par.pods, "data": par.data, "model": par.model}

    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for arr, spec in zip(flat_p, flat_s):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = 1
            for a in axes:
                total *= sizes[a]
            assert arr.shape[dim] % total == 0, (arch, spec, arr.shape, dim)


@pytest.mark.parametrize("arch", ["deepseek-67b", "recurrentgemma-2b",
                                  "mamba2-370m", "qwen1.5-0.5b"])
def test_cache_specs_divisible(arch):
    from repro.models.transformer import init_caches
    model_cfg = get_model_config(arch)
    par = get_parallel_config(arch, multi_pod=False)
    rules = ShardingRules(model_cfg, par)
    caches = jax.eval_shape(
        lambda: init_caches(model_cfg, 128, 32768, jnp.bfloat16))
    specs = rules.cache_tree_specs(caches)
    sizes = {"pod": par.pods, "data": par.data, "model": par.model}
    flat_c = jax.tree.leaves(caches)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for arr, spec in zip(flat_c, flat_s):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = 1
            for a in axes:
                total *= sizes[a]
            assert arr.shape[dim] % total == 0, (arch, spec, arr.shape)


# ------------------------- compression -------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 5), st.integers(3, 4000))
def test_quantize_roundtrip_error_bound(seed, n):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32) * 10)
    q, scale = quantize_int8(x)
    deq = dequantize_int8(q, scale, x.shape, jnp.float32)
    # per-chunk max-abs scaling: |err| <= scale/2 per chunk
    err = np.abs(np.asarray(deq - x))
    bound = np.asarray(scale).max() / 2 + 1e-6
    assert err.max() <= bound


def test_error_feedback_accumulates_residual():
    x = jnp.asarray(np.linspace(-1, 1, 100).astype(np.float32))
    err = jnp.zeros_like(x)
    q, scale, err2 = compress_with_feedback(x, err)
    deq = dequantize_int8(q, scale, x.shape, jnp.float32)
    np.testing.assert_allclose(np.asarray(err2), np.asarray(x - deq),
                               atol=1e-6)


def test_error_feedback_unbiased_over_time():
    """Repeatedly compressing the SAME gradient with error feedback must
    recover the true value in the long-run average (the EF guarantee)."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(256).astype(np.float32))
    err = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    n = 50
    for _ in range(n):
        q, scale, err = compress_with_feedback(g, err)
        total = total + dequantize_int8(q, scale, g.shape, jnp.float32)
    avg = total / n
    assert float(jnp.abs(avg - g).max()) < 5e-3


# ------------------------- multi-device (subprocess) -------------------------

# The subprocess scripts drive the multi-device code on 8 virtual CPU
# devices: an all-Auto mesh (repro.launch.mesh.make_mesh) entered with
# jax.set_mesh.

_SUBPROC = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.parallel import make_hierarchical_allreduce
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    g = {"a": jnp.arange(37, dtype=jnp.float32) * 0.1,
         "b": jnp.ones((5, 3), jnp.bfloat16)}
    errs = jax.tree.map(lambda x: jnp.zeros_like(x, dtype=jnp.float32), g)
    with jax.set_mesh(mesh):
        out, _ = jax.jit(make_hierarchical_allreduce(mesh))(g, errs)
        assert float(jnp.abs(out["a"] - g["a"]).max()) < 1e-6
        outc, ne = jax.jit(make_hierarchical_allreduce(mesh, compress=True))(g, errs)
        rel = float(jnp.abs(outc["a"] - g["a"]).max() / jnp.abs(g["a"]).max())
        assert rel < 0.02, rel
    print("MULTIDEVICE_OK")
""")


def test_hierarchical_allreduce_8dev():
    r = subprocess.run([sys.executable, "-c", _SUBPROC], capture_output=True,
                       text=True, cwd=".", timeout=300)
    assert "MULTIDEVICE_OK" in r.stdout, r.stdout + r.stderr


_SUBPROC_MOE = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, "src")
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.config import get_model_config
    from repro.models.moe import apply_moe, init_moe
    from repro.launch.mesh import make_mesh
    cfg = dataclasses.replace(
        get_model_config("phi3.5-moe-42b-a6.6b", smoke=True),
        act_dtype="float32", param_dtype="float32", moe_capacity_factor=8.0)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
    y_flat, _ = apply_moe(p, x, cfg)                 # ungrouped reference
    cfg_g = dataclasses.replace(cfg, moe_group_by_batch=True)
    with jax.set_mesh(mesh):
        y_grp, aux = jax.jit(lambda x, p: apply_moe(p, x, cfg_g))(x, p)
    err = float(jnp.abs(y_flat - y_grp).max())
    assert err < 1e-5, err
    print("MOE_SHARDMAP_OK")
""")


def test_grouped_moe_shardmap_8dev():
    """The §Perf hillclimb path: full-manual shard_map MoE routing must match
    the flat dispatch exactly when capacity is ample (8-device mesh)."""
    r = subprocess.run([sys.executable, "-c", _SUBPROC_MOE],
                       capture_output=True, text=True, cwd=".", timeout=300)
    assert "MOE_SHARDMAP_OK" in r.stdout, r.stdout + r.stderr


def test_compat_shard_map_single_device():
    """The mesh calls the multi-device code makes, in-process on one
    device: jax.shard_map over a trivial mesh reduces correctly, and
    jax.set_mesh is what jax.sharding.get_abstract_mesh reads back (the
    ambient mesh the grouped-MoE path keys on)."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("x",))
    f = jax.shard_map(lambda v: jax.lax.psum(v, "x"), mesh=mesh,
                      in_specs=P("x"), out_specs=P(None))
    out = f(jnp.arange(4, dtype=jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.arange(4, dtype=np.float32))
    assert jax.sharding.get_abstract_mesh().empty
    with jax.set_mesh(mesh):
        amb = jax.sharding.get_abstract_mesh()
        assert not amb.empty and "x" in amb.axis_names
    assert jax.sharding.get_abstract_mesh().empty
