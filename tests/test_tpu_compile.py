"""Compile guards for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what CPU runs and Pallas interpret mode accept:
block shapes off the (8, 128) tiling, primitives Mosaic cannot lower, a
program that does not fit the device. These tests compile the main paths
for one chip of a described ``v5e:2x2`` at real sizes: the batched netsim
step at the Fig. 3 grid shape with the chip's input donation, and the three
Pallas kernels at published widths through the public wrappers (which pick
the Mosaic kernel when lowering for a TPU). Nothing runs, so they say
nothing of results or times.

The topology is described inside a fixture only: the TPU library admits
one process at a time, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=sharding), tree)


def _fig3_grid(num_paths: int = 1):
    """The Fig. 3 grid of ``chip_smoke.py``: 7 distances, congestion
    workload, 220 ms horizon (44,000 steps)."""
    from repro.config.base import NetConfig
    from repro.netsim.runner import convergence_horizon_us
    from repro.netsim.workload import congestion_workload
    cfgs = [NetConfig(distance_km=d, num_paths=num_paths)
            for d in (1.0, 10.0, 50.0, 100.0, 300.0, 500.0, 1000.0)]
    horizon = max(convergence_horizon_us(cfgs), 30_000.0)
    wl = congestion_workload(num_inter=4, num_intra=4,
                             burst_start_us=horizon / 3.0,
                             burst_len_us=horizon / 3.0, horizon_us=horizon)
    return cfgs, wl, horizon


@pytest.mark.parametrize("scheme,mode,num_paths", [
    ("dcqcn", "metrics", 1),
    ("matchrdma", "metrics", 1),
    ("matchrdma", "full", 1),
    ("rdmacell", "metrics", 3),
])
def test_netsim_batch_step_compiles_for_v5e(one_chip, scheme, mode,
                                            num_paths):
    from repro.config.base import batch_template, stack_net_params
    from repro.netsim import fluid
    from repro.netsim.channel import get_channel_model
    from repro.netsim.schemes import get_scheme
    from repro.netsim.workload import as_workload_batch

    cfgs, wl, horizon = _fig3_grid(num_paths)
    tmpl = batch_template(cfgs)
    steps = tmpl.horizon_steps(horizon)
    assert steps == 44_000
    delay_pad, history_slots = fluid.batch_padding(cfgs)
    params = _shapes(stack_net_params(cfgs), one_chip)
    wlp = _shapes(as_workload_batch(wl, len(cfgs)), one_chip)
    jitted = fluid._jit_traced_batch(donate_argnums=(1, 2))
    compiled = jitted.lower(
        tmpl, params, wlp, get_scheme(scheme), steps, 0, delay_pad,
        history_slots, mode, 1, int(steps * fluid.WARMUP_FRAC),
        get_channel_model(None)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes > 0
    # the donated inputs alias outputs, as they do on the chip
    assert 0 < mem.alias_size_in_bytes <= mem.argument_size_in_bytes
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 1 << 30


def _qwen_attention(sharding):
    from repro.config import get_model_config
    from repro.kernels import flash_attention
    cfg = get_model_config("qwen1.5-0.5b")
    d = cfg.d_model // cfg.num_heads
    q = jax.ShapeDtypeStruct((1, 2048, cfg.num_heads, d), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((1, 2048, cfg.num_kv_heads, d), jnp.bfloat16,
                              sharding=sharding)
    return flash_attention, (q, kv, kv)


def _mamba2_ssd(sharding):
    from repro.config import get_model_config
    from repro.kernels import ssd_scan
    cfg = get_model_config("mamba2-370m")
    s, p = 2048, cfg.ssm_headdim
    h = cfg.ssm_expand * cfg.d_model // p

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    def fn(x, dt, A, B, C):
        return ssd_scan(x, dt, A, B, C, chunk=cfg.ssm_chunk)

    return fn, (arg(1, s, h, p), arg(1, s, h), arg(h),
                arg(1, s, 1, cfg.ssm_state), arg(1, s, 1, cfg.ssm_state))


def _recurrentgemma_rglru(sharding):
    from repro.config import get_model_config
    from repro.kernels import rglru_recurrence
    cfg = get_model_config("recurrentgemma-2b")
    ab = jax.ShapeDtypeStruct((1, 2048, cfg.rglru_width), jnp.float32,
                              sharding=sharding)
    return rglru_recurrence, (ab, ab)


@pytest.mark.parametrize("build", [_qwen_attention, _mamba2_ssd,
                                   _recurrentgemma_rglru],
                         ids=["flash_attention-qwen1.5-0.5b",
                              "ssd_scan-mamba2-370m",
                              "rglru_scan-recurrentgemma-2b"])
def test_pallas_kernel_compiles_for_v5e(one_chip, build):
    fn, args = build(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
