import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes and record memory / cost / collective
statistics for the roofline analysis.

MUST be run as its own process (the XLA_FLAGS line above has to execute
before any other jax import in the interpreter):

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k --mesh single

Results land in experiments/dryrun/<arch>.<shape>.<mesh>.json; benchmarks/
roofline_table.py and EXPERIMENTS.md read them.
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (build_prefill_step, build_serve_step,
                                build_train_step)
from repro.models import init_cache, init_params
from repro.models.config import SHAPES, param_count, active_param_count
from repro.models.inputs import decode_token_spec, train_batch_spec
from repro.optim import adamw_init
from repro.runtime import sharding as shr
from repro.runtime import jaxpr_cost
from repro.runtime.hlo_collectives import collective_bytes as hlo_collective_bytes

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# Grad-accumulation microbatch count per arch for train_4k (activation
# memory control on 16 GB/chip targets).
MICROBATCHES = {
    "arctic-480b": 8, "jamba-v0.1-52b": 8, "mixtral-8x7b": 8,
    "pixtral-12b": 8, "qwen3-8b": 8, "gemma-7b": 8, "tinyllama-1.1b": 8,
    "stablelm-1.6b": 8, "mamba2-1.3b": 8, "whisper-base": 8,
}

COLLECTIVE_RE = re.compile(
    r"(f8e4m3fn|f8e5m2|bf16|f16|f32|f64|u8|u16|u32|u64|s8|s16|s32|s64|pred)"
    r"\[([0-9,]*)\][^ ]* (all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)")

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
               "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
               "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of collective ops in the optimized HLO, per
    collective kind. (Result bytes ~= moved bytes for all-reduce/permute;
    an upper bound for all-gather where the result includes local shards.)"""
    out = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind] = out.get(kind, 0) + n * DTYPE_BYTES[dt]
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def should_skip(arch: str, shape_name: str, cfg) -> str:
    if shape_name == "long_500k" and not cfg.supports_long_context:
        return ("full-attention arch: O(S^2) prefill / full 500k cache "
                "decode excluded by design (DESIGN.md long_500k table)")
    return ""


def abstract_state(cfg, spec):
    """ShapeDtypeStruct pytrees for params / optimizer / cache: nothing is
    allocated (jax.eval_shape all the way)."""
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw_init, params)
    return params, opt


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: Path = OUT_DIR, verbose: bool = True,
             variant: str = "") -> dict:
    """variant="int8serve": decode cells store projections INT8 in HBM
    (the DB-PIM/FTA serving format) — §Perf hillclimb for weight-bound
    decode."""
    cfg = get_config(arch)
    if variant == "dotsremat":
        cfg = cfg.scaled(remat_policy="dots")
    spec = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "status": "ok", "variant": variant,
        "params": param_count(cfg), "active_params": active_param_count(cfg),
    }
    skip = should_skip(arch, shape_name, cfg)
    if skip:
        rec["status"] = "skip"
        rec["reason"] = skip
        _write(rec, out_dir)
        return rec

    t0 = time.time()
    try:
        params_abs, opt_abs = abstract_state(cfg, spec)
        with mesh:
            if spec.kind == "train":
                mb = MICROBATCHES.get(arch, 1) if shape_name == "train_4k" else 1
                step, shard_fn = build_train_step(cfg, mesh, microbatches=mb)
                batch_abs = train_batch_spec(cfg, spec.global_batch,
                                             spec.seq_len)
                pspec, ospec, bspec = shard_fn(params_abs, opt_abs, batch_abs)
                jitted = jax.jit(
                    step,
                    in_shardings=(shr.named(pspec, mesh),
                                  shr.named(ospec, mesh),
                                  shr.named(bspec, mesh)),
                    donate_argnums=(0, 1))
                lowered = jitted.lower(params_abs, opt_abs, batch_abs)
                rec["microbatches"] = mb
                rec["jaxpr_cost"] = jaxpr_cost.analyze(
                    step, params_abs, opt_abs, batch_abs)
            elif spec.kind == "prefill":
                step, shard_fn = build_prefill_step(cfg, mesh)
                batch_abs = train_batch_spec(cfg, spec.global_batch,
                                             spec.seq_len)
                batch_abs.pop("labels")
                pspec, bspec = shard_fn(params_abs, batch_abs)
                jitted = jax.jit(step,
                                 in_shardings=(shr.named(pspec, mesh),
                                               shr.named(bspec, mesh)))
                lowered = jitted.lower(params_abs, batch_abs)
                rec["jaxpr_cost"] = jaxpr_cost.analyze(
                    step, params_abs, batch_abs)
            else:  # decode
                step, shard_fn = build_serve_step(
                    cfg, mesh, int8_weights=(variant == "int8serve"))
                if variant == "int8serve":
                    from repro.sparsity.sparse_linear import \
                        quantize_params_for_serving
                    params_abs = jax.eval_shape(quantize_params_for_serving,
                                                params_abs)
                enc_abs = None
                if cfg.is_encdec:
                    enc_abs = jax.ShapeDtypeStruct(
                        (spec.global_batch, cfg.encoder_seq, cfg.d_model),
                        jnp.bfloat16)
                cache_abs = jax.eval_shape(
                    lambda: init_cache(cfg, spec.global_batch, spec.seq_len,
                                       enc_out=enc_abs))
                token_abs = jax.ShapeDtypeStruct((spec.global_batch, 1),
                                                 jnp.int32)
                pspec, _, cspec, tspec = shard_fn(params_abs, None,
                                                  cache_abs, token_abs)
                jitted = jax.jit(step,
                                 in_shardings=(shr.named(pspec, mesh),
                                               None,
                                               shr.named(cspec, mesh),
                                               shr.named(tspec, mesh)),
                                 donate_argnums=(2,))
                lowered = jitted.lower(params_abs, None, cache_abs,
                                       token_abs)
                rec["jaxpr_cost"] = jaxpr_cost.analyze(
                    step, params_abs, None, cache_abs, token_abs)

            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)

            mem = compiled.memory_analysis()
            rec["memory_analysis"] = {
                k: int(getattr(mem, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)}
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            rec["cost_analysis"] = {
                k: float(v) for k, v in dict(ca or {}).items()
                if isinstance(v, (int, float)) and (
                    k in ("flops", "bytes accessed", "transcendentals")
                    or k.startswith("bytes accessed"))}
            hlo = compiled.as_text()
            rec["collectives_once"] = collective_bytes(hlo)
            rec["collectives"] = hlo_collective_bytes(hlo)
            rec["hlo_bytes"] = len(hlo)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)
    _write(rec, out_dir)
    if verbose:
        msg = rec["status"]
        if rec["status"] == "ok":
            flops = rec.get("jaxpr_cost", {}).get("dot_flops", 0)
            msg += (f" flops={flops:.3e} "
                    f"coll={rec['collectives'].get('total', 0):.3e}B "
                    f"compile={rec.get('compile_s')}s")
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: {msg}",
              flush=True)
    return rec


def _write(rec, out_dir: Path):
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = f".{rec['variant']}" if rec.get("variant") else ""
    path = out_dir / f"{rec['arch']}.{rec['shape']}.{rec['mesh']}{suffix}.json"
    slim = {k: v for k, v in rec.items() if k != "traceback"}
    path.write_text(json.dumps(slim, indent=1))
    if "traceback" in rec:
        (out_dir / (path.stem + ".err.txt")).write_text(rec["traceback"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=[None, "single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.mesh] if args.mesh else ["single", "multi"]

    n_ok = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                path = OUT_DIR / f"{arch}.{shape}.{mesh_kind}.json"
                if args.skip_existing and path.exists():
                    prev = json.loads(path.read_text())
                    if prev.get("status") in ("ok", "skip"):
                        continue
                rec = run_cell(arch, shape, mesh_kind,
                               variant=args.variant)
                if rec["status"] == "error":
                    n_err += 1
                else:
                    n_ok += 1
    print(f"[dryrun] done: {n_ok} ok/skip, {n_err} errors", flush=True)
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
