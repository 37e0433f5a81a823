import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on 512 placeholder devices; record memory/cost/collective
analysis for the roofline.

MUST be run as a script / -m module (the XLA_FLAGS line above has to execute
before any jax import anywhere in the process).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b \
        --shape train_4k --mesh single --out results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import (
    SHAPES, get_model_config, get_parallel_config, list_archs,
    shape_applicable,
)
from repro.config.base import TrainConfig
from repro.launch.hlo_analysis import collective_summary
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    decode_input_specs, params_and_opt_specs, prefill_input_specs,
    train_input_specs,
)
from repro.models import build_model
from repro.parallel.sharding import named
from repro.train.optimizer import adam_update, clip_by_global_norm

# v5e-like hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link (intra-pod)
OTN_BW = 16 * 100e9 / 8.0    # inter-DC aggregate per pod pair (16x100G)


def _mem_analysis(compiled):
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return {}
        out = {}
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
        return out
    except Exception:
        return {}


def _cost_analysis(compiled):
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    k in ("flops", "bytes accessed", "transcendentals",
                          "optimal_seconds")
                    or k.startswith("bytes accessed"))}
    except Exception:
        return {}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             include_hlo_text: bool = False) -> dict:
    t0 = time.time()
    shape = SHAPES[shape_name]
    model_cfg = get_model_config(arch)
    par = get_parallel_config(arch, multi_pod=multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = par.num_devices

    model = build_model(model_cfg, remat=par.remat)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": shape.kind,
        "params": model_cfg.param_count(),
        "active_params": model_cfg.active_param_count(),
    }

    if not shape_applicable(model_cfg, shape):
        result["status"] = "SKIP(full-attention)"
        return result

    params_s, params_p, opt_s, opt_p = params_and_opt_specs(model, par)

    if shape.kind == "train":
        train_cfg = TrainConfig(global_batch=shape.global_batch,
                                seq_len=shape.seq_len)
        batch_s, batch_p = train_input_specs(model_cfg, par, shape)

        micro = max(par.microbatches, 1)

        def train_step(params, opt_state, batch):
            if micro > 1:
                mb = {k: v.reshape(micro, v.shape[0] // micro, *v.shape[1:])
                      for k, v in batch.items()}

                def acc(carry, one):
                    gsum, lsum = carry
                    (l, _), g = jax.value_and_grad(
                        model.loss_fn, has_aux=True)(params, one)
                    return (jax.tree.map(jnp.add, gsum, g), lsum + l), None

                g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
                (grads, lsum), _ = jax.lax.scan(
                    acc, (g0, jnp.float32(0.0)), mb)
                grads = jax.tree.map(lambda g: g / micro, grads)
                loss = lsum / micro
            else:
                (loss, metrics), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(params, batch)
            grads, gnorm = clip_by_global_norm(grads, train_cfg.grad_clip)
            params, opt_state, om = adam_update(grads=grads, params=params,
                                                state=opt_state, cfg=train_cfg)
            return params, opt_state, {"loss": loss, "grad_norm": gnorm}

        in_sh = (named(mesh, params_p), named(mesh, opt_p), named(mesh, batch_p))
        out_sh = (named(mesh, params_p), named(mesh, opt_p), None)
        fn = jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0, 1))
        with jax.set_mesh(mesh):
            lowered = fn.lower(params_s, opt_s, batch_s)
        tokens = shape.global_batch * shape.seq_len
        result["model_flops"] = 6.0 * model_cfg.active_param_count() * tokens

    elif shape.kind == "prefill":
        inp_s, inp_p = prefill_input_specs(model_cfg, par, shape)

        def prefill_step(params, inputs):
            caches, logits = model.prefill(params, inputs,
                                           max_len=shape.seq_len)
            return caches, logits

        fn = jax.jit(prefill_step,
                     in_shardings=(named(mesh, params_p), named(mesh, inp_p)))
        with jax.set_mesh(mesh):
            lowered = fn.lower(params_s, inp_s)
        tokens = shape.global_batch * shape.seq_len
        result["model_flops"] = 2.0 * model_cfg.active_param_count() * tokens

    else:  # decode / long_decode
        cache_s, cache_p, inp_s, inp_p, pos_s = decode_input_specs(
            model_cfg, par, shape)

        def serve_step(params, caches, inp, pos):
            caches, logits = model.decode_step(params, caches, inp, pos)
            return caches, jnp.argmax(logits, -1).astype(jnp.int32)

        fn = jax.jit(serve_step,
                     in_shardings=(named(mesh, params_p), named(mesh, cache_p),
                                   named(mesh, inp_p), None),
                     donate_argnums=(1,))
        with jax.set_mesh(mesh):
            lowered = fn.lower(params_s, cache_s, inp_s, pos_s)
        result["model_flops"] = 2.0 * model_cfg.active_param_count() * shape.global_batch

    t_lower = time.time()
    compiled = lowered.compile()
    t_compile = time.time()

    result.update(_mem_analysis(compiled))
    cost = _cost_analysis(compiled)
    result["cost_analysis"] = cost

    hlo = compiled.as_text()
    result.update(collective_summary(hlo, multi_pod))
    if include_hlo_text:
        result["hlo_len"] = len(hlo)

    # ---- roofline terms (per device, seconds) ----
    # trip-count-aware parsed values (cost_analysis counts while bodies once)
    flops_dev = max(result.get("hlo_dot_flops_per_device", 0.0),
                    cost.get("flops", 0.0))
    bytes_dev = max(result.get("hlo_hbm_bytes_per_device", 0.0),
                    cost.get("bytes accessed", 0.0))
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_intra = result.get("intra_pod_bytes_per_device", 0.0) / ICI_BW
    # inter-pod: per-device bytes x 256 chips share the 16x100G OTN pipe
    inter_dev = result.get("inter_pod_bytes_per_device", 0.0)
    t_inter = inter_dev * 256 / OTN_BW if multi_pod else 0.0
    t_coll = t_intra + t_inter
    result["roofline"] = {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "t_coll_intra_s": t_intra,
        "t_coll_inter_s": t_inter,
        "dominant": max(
            [("compute", t_compute), ("memory", t_memory),
             ("collective", t_coll)], key=lambda kv: kv[1])[0],
        "useful_flops_ratio": (result["model_flops"] / (chips * flops_dev)
                               if flops_dev else 0.0),
    }
    result["lower_s"] = round(t_lower - t0, 2)
    result["compile_s"] = round(t_compile - t_lower, 2)
    result["status"] = "OK"
    return result


def cell_name(arch, shape, multi_pod):
    m = "multi" if multi_pod else "single"
    return f"{arch}__{shape}__{m}".replace("/", "_")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                name = cell_name(arch, shape, mp)
                path = os.path.join(args.out, name + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip-cached] {name}")
                    continue
                print(f"[run] {name}", flush=True)
                try:
                    res = run_cell(arch, shape, mp)
                except Exception as e:  # noqa: BLE001
                    res = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": f"FAIL: {type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                st = res.get("status")
                rf = res.get("roofline", {})
                print(f"  -> {st} compile={res.get('compile_s', '-')}s "
                      f"dominant={rf.get('dominant', '-')}", flush=True)


if __name__ == "__main__":
    main()
