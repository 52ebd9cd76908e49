import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.

import argparse
import json
import math
import pathlib
import sys
import time

import jax.numpy as jnp

from repro.configs import DONN_ARCHS, LM_ARCHS
from repro.core.config import DONNConfig
from repro.launch import mesh as mesh_mod
from repro.launch.specs import cell_status, input_specs, shapes_for
from repro.models import lm
from repro.models.config import get_config
from repro.nn import param_count
from repro.runtime import sharding as shd
from repro.runtime import steps as steps_mod
from repro.runtime.hlo_analysis import analyze

HBM_PER_CHIP = 16e9  # TPU v5e

# Per-cell memory-feasibility overrides (documented in EXPERIMENTS.md):
# microbatched gradient accumulation and/or reduced-precision optimizer
# state for the cells whose exact-f32 footprint exceeds v5e HBM on the
# single pod.  Keys: (arch, shape, multi_pod) — pod2 gets ZeRO-across-pods
# from the ("data", "pod") FSDP rule and usually needs no override.
OVERRIDES = {
    ("mixtral-8x7b", "train_4k", False): dict(accum_steps=2),
    ("mixtral-8x7b", "train_4k", True): dict(accum_steps=2),
    ("llama-3.2-vision-11b", "train_4k", False): dict(
        accum_steps=8, state_dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,  # halves the 8x-microbatched gathers
        # (cast-at-use keeps f32 gathers: GSPMD gathers before converting)
    ),
    ("llama-3.2-vision-11b", "train_4k", True): dict(accum_steps=2),
    ("recurrentgemma-9b", "train_4k", False): dict(accum_steps=2),
    ("arctic-480b", "train_4k", False): dict(
        accum_steps=8, param_dtype=jnp.bfloat16, state_dtype=jnp.bfloat16,
        accum_dtype=jnp.bfloat16,
    ),
    ("arctic-480b", "train_4k", True): dict(accum_steps=4),
}

# Inference-side overrides: serving holds bf16 params (no f32 masters).
PREFILL_OVERRIDES = {
    ("arctic-480b", "prefill_32k"): dict(param_dtype=jnp.bfloat16),
}


# ----------------------------------------------------------- model flops
def lm_model_flops(cfg, kind: str, cell) -> tuple:
    """(N_total, N_active, MODEL_FLOPS) for the 6ND convention."""
    n = param_count(lm.param_specs(cfg))
    n_active = n
    if cfg.family == "moe":
        f = cfg.expert_d_ff or cfg.d_ff
        expert_params = cfg.n_layers * cfg.n_experts * 3 * cfg.d_model * f
        n_active = n - expert_params * (cfg.n_experts - cfg.top_k) / cfg.n_experts
    tokens = {
        "train": cell.global_batch * cell.seq_len,
        "prefill": cell.global_batch * cell.seq_len,
        "decode": cell.global_batch,  # one new token per sequence
    }[kind]
    mult = 6.0 if kind == "train" else 2.0
    return n, n_active, mult * n_active * tokens


def donn_model_flops(cfg: DONNConfig, batch: int) -> tuple:
    """FFT2+iFFT2+ComplexMM per layer, x3 for fwd+bwd (train)."""
    n = cfg.n
    fft2 = 10.0 * n * n * math.log2(max(n, 2))  # ~5 N log N per 1-D line, 2N lines
    per_layer = 2.0 * fft2 + 6.0 * n * n  # FFT2 + iFFT2 + complex multiply
    hops = cfg.depth + 1
    chans = max(cfg.channels, 1)
    n_params = cfg.depth * n * n * chans
    flops = 3.0 * batch * chans * hops * per_layer  # train: fwd + ~2x bwd
    return n_params, n_params, flops


# ------------------------------------------------------------- one cell
def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: pathlib.Path,
             smoke: bool = False) -> dict:
    t0 = time.time()
    mesh_name = "pod2-512" if multi_pod else "pod1-256"
    cfg, cell, kind, specs = input_specs(arch, shape, smoke=smoke)
    rec = {
        "arch": arch, "shape": shape, "kind": kind, "mesh": mesh_name,
        "seq_len": cell.seq_len, "global_batch": cell.global_batch,
    }
    skip = cell_status(cfg, cell)
    if skip:
        rec["status"] = skip
        return rec

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.devices.shape)
    is_donn = isinstance(cfg, DONNConfig)

    with mesh:
        if is_donn:
            # production DONN path: shard_map DP (local FFTs) — the
            # auto-sharded pjit variant is preserved as the §Perf baseline
            from repro.runtime.donn_steps import (
                compile_donn_train_step_shardmap,
            )

            fn, s_shard, b_shard, sspecs = compile_donn_train_step_shardmap(
                cfg, mesh, global_batch=cell.global_batch
            )
            state_abs = shd.abstract_like(sspecs)
            lowered = fn.lower(state_abs, specs)
        elif kind == "train":
            over = OVERRIDES.get((arch, shape, multi_pod), {})
            if over:
                rec["overrides"] = {
                    k: getattr(v, "__name__", str(v)) for k, v in over.items()
                }
            fn, s_shard, b_shard, sspecs = steps_mod.compile_train_step(
                cfg, mesh, specs, **over
            )
            state_abs = shd.abstract_like(sspecs)
            lowered = fn.lower(state_abs, specs)
        elif kind == "prefill":
            pover = PREFILL_OVERRIDES.get((arch, shape), {})
            if pover:
                rec["overrides"] = {
                    k: getattr(v, "__name__", str(v)) for k, v in pover.items()
                }
            fn, p_shard, b_shard, pspecs = steps_mod.compile_prefill_step(
                cfg, mesh, specs, **pover
            )
            params_abs = shd.abstract_like(pspecs)
            lowered = fn.lower(params_abs, specs)
        else:  # decode
            L = specs["cache"]["k"].shape[2] if "k" in specs["cache"] else 0
            fn, p_shard, c_shard, cspecs = steps_mod.compile_decode_step(
                cfg, mesh, cell.global_batch, cell.seq_len
            )
            params_abs = shd.abstract_like(lm.param_specs(cfg))
            lowered = fn.lower(
                params_abs, specs["cache"], specs["tokens"], specs["pos"]
            )
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    print(mem)  # proves it fits (per-device bytes)
    xla_cost = compiled.cost_analysis()
    print({k: xla_cost[k] for k in ("flops", "bytes accessed") if k in xla_cost})
    hlo = analyze(compiled.as_text())

    if is_donn:
        n_total, n_active, model_flops = donn_model_flops(cfg, cell.global_batch)
    else:
        n_total, n_active, model_flops = lm_model_flops(cfg, kind, cell)

    per_dev_bytes = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    compute_s = hlo.flops / mesh_mod.PEAK_FLOPS_BF16
    memory_s = hlo.bytes / mesh_mod.HBM_BW
    collective_s = hlo.collective_bytes / mesh_mod.ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    bound_s = max(terms.values())
    rec.update({
        "status": "ok",
        "chips": chips,
        "n_params": n_total,
        "n_active_params": n_active,
        "model_flops": model_flops,
        "hlo_flops_per_dev": hlo.flops,
        "hlo_dot_flops_per_dev": hlo.dot_flops,
        "hlo_bytes_per_dev": hlo.bytes,
        "collective_bytes_per_dev": hlo.collective_bytes,
        "collective_breakdown": hlo.collective_breakdown,
        "terms": terms,
        "dominant": dominant,
        "roofline_fraction": (
            (model_flops / chips / mesh_mod.PEAK_FLOPS_BF16) / bound_s
            if bound_s > 0 else 0.0
        ),
        "model_over_hlo_flops": (
            model_flops / (hlo.flops * chips) if hlo.flops else 0.0
        ),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_bytes": per_dev_bytes,
            "fits_16GiB_hbm": bool(per_dev_bytes <= HBM_PER_CHIP),
        },
        "xla_cost_raw": {
            "flops_no_tripcount": xla_cost.get("flops"),
            "bytes_no_tripcount": xla_cost.get("bytes accessed"),
        },
        "compile_wall_s": time.time() - t0,
    })
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run driver")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells = []
        for arch in LM_ARCHS + DONN_ARCHS:
            cfg = get_config(arch)
            for cell in shapes_for(cfg):
                cells.append((arch, cell.name))
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = 0
    for arch, shape in cells:
        for multi in meshes:
            tag = f"{arch}__{shape}__{'pod2' if multi else 'pod1'}"
            path = out_dir / f"{tag}.json"
            if path.exists():
                print(f"[skip-cached] {tag}")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                rec = run_cell(arch, shape, multi, out_dir, smoke=args.smoke)
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                rec = {
                    "arch": arch, "shape": shape,
                    "mesh": "pod2-512" if multi else "pod1-256",
                    "status": f"FAIL: {type(e).__name__}: {e}",
                }
                failures += 1
            path.write_text(json.dumps(rec, indent=2, default=float))
            print(f"[done] {tag}: {rec.get('status')}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
