import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# Multi-pod dry-run: lower + compile every (architecture x input shape)
# on the production meshes, with no device allocation (ShapeDtypeStruct
# inputs only).  NOTE: the XLA_FLAGS line above MUST run before any jax
# import (device count locks on first init), hence no module docstring.
#
# For each pair this prints/records:
#   * compiled.memory_analysis()  — proves the sharded program fits,
#   * compiled.cost_analysis()    — FLOPs/bytes for §Roofline,
#   * collective-bytes breakdown parsed from the compiled HLO.
#
# Usage:
#   python -m repro.launch.dryrun --arch mistral-large-123b --shape train_4k
#   python -m repro.launch.dryrun --multi-pod --out results.jsonl
import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, INPUT_SHAPES, build_model, get_config
from repro.configs.base import ArchConfig, InputShape
from repro.launch import specs as speclib
from repro.launch.mesh import make_production_mesh
from repro.optim import get_optimizer
from repro.train.steps import (
    make_prefill_step,
    make_serve_step,
    make_train_step,
)

# --- HLO collective-bytes accounting -------------------------------------------------
_COLLECTIVE_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*(\(?[^=]*?\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(", re.M,
)
_SHAPE_RE = re.compile(r"(bf16|f32|f16|f64|s32|u32|s8|u8|pred|s64|u64)"
                       r"\[([\d,]*)\]")
_DTYPE_BYTES = {
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4, "u32": 4,
    "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8,
}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output-shape bytes of every collective op in the HLO."""
    out: Dict[str, float] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shapes_str, kind, suffix = m.group(2), m.group(3), m.group(4)
        if suffix == "-done":
            continue  # counted at -start
        total = 0
        for sm in _SHAPE_RE.finditer(shapes_str):
            dt, dims = sm.group(1), sm.group(2)
            n = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + total
    return out


# --- per-pair dry run ------------------------------------------------------------------
def lower_pair(
    arch: str,
    shape_name: str,
    mesh,
    *,
    cfg: Optional[ArchConfig] = None,
    fsdp_axes=None,
    sharding_mode: str = "fsdp2d",   # or "zero1" (EXPERIMENTS.md §Perf)
    donate: bool = True,
):
    """Build and lower the right step for (arch, shape) on a mesh.

    Returns (lowered, meta) where meta records what was lowered.
    """
    cfg = cfg or get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    window = speclib.sliding_window_for(cfg, shape)
    # chunked = flash-style online-softmax attention in pure XLA: the
    # production path for full-sequence shapes (never materializes SxS).
    attn_impl = "chunked" if shape.kind in ("train", "prefill") else "xla"
    model = build_model(cfg, sliding_window=window, attn_impl=attn_impl)
    fsdp_axes = fsdp_axes or tuple(
        a for a in ("data",) if a in mesh.axis_names
    )

    meta = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "kind": shape.kind, "window": window,
    }

    if sharding_mode == "zero1":
        param_axes, opt_axes = (), ("data",)
    else:
        param_axes, opt_axes = fsdp_axes, fsdp_axes
    meta["sharding"] = sharding_mode

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            state_sds = speclib.state_specs(model, cfg, mesh, param_axes,
                                            opt_fsdp_axes=opt_axes)
            batch_sds = speclib.batch_specs(cfg, shape, mesh)
            opt = get_optimizer(cfg.optimizer, cfg.learning_rate)
            step = make_train_step(model, opt)
            fn = jax.jit(step, donate_argnums=(0,) if donate else ())
            lowered = fn.lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            p_sds = speclib.params_specs(model, mesh, param_axes)
            batch_sds = speclib.batch_specs(cfg, shape, mesh)
            step = make_prefill_step(model)
            lowered = jax.jit(step).lower(p_sds, batch_sds)
        else:  # decode
            p_sds = speclib.params_specs(model, mesh, param_axes)
            cache_sds = speclib.cache_specs(model, cfg, shape, mesh,
                                            param_axes)
            tok_sds = speclib.token_specs(cfg, shape, mesh)
            pos_sds = speclib.sds((), jnp.int32, mesh)
            step = make_serve_step(model)
            fn = jax.jit(step, donate_argnums=(2,) if donate else ())
            lowered = fn.lower(p_sds, tok_sds, cache_sds, pos_sds)
    return lowered, meta


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             verbose: bool = True, cfg: Optional[ArchConfig] = None
             ) -> Dict[str, Any]:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    lowered, meta = lower_pair(arch, shape_name, mesh, cfg=cfg)
    t_lower = time.time() - t0

    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    result = dict(meta)
    result.update(
        {
            "ok": True,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "flops": float(cost.get("flops", -1)) if cost else None,
            "bytes_accessed": float(cost.get("bytes accessed", -1))
            if cost else None,
            "collective_bytes": coll,
            "memory": _mem_dict(mem),
        }
    )
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh={result['mesh']}  "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print(f"  memory_analysis: {result['memory']}")
        print(f"  cost_analysis: flops={result['flops']:.3e} "
              f"bytes={result['bytes_accessed']:.3e}")
        print(f"  collectives: { {k: f'{v:.3e}' for k, v in coll.items()} }")
    return result


def _mem_dict(mem) -> Optional[Dict[str, float]]:
    if mem is None:
        return None
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = float(v)
    return out or {"repr": str(mem)[:500]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS) + ["all"],
                    default="all")
    ap.add_argument("--shape", choices=list(INPUT_SHAPES) + ["all"],
                    default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None, help="append-JSONL output path")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]

    done = set()
    if args.out and args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("ok"):
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass

    n_ok = n_fail = 0
    for arch in archs:
        for shape in shapes:
            mesh_tag = "2x16x16" if args.multi_pod else "16x16"
            if (arch, shape, mesh_tag) in done:
                print(f"[dryrun] skip {arch} x {shape} (cached)")
                continue
            try:
                res = run_pair(arch, shape, multi_pod=args.multi_pod)
                n_ok += 1
            except Exception as e:
                traceback.print_exc()
                res = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                       "ok": False, "error": f"{type(e).__name__}: {e}"}
                n_fail += 1
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(res) + "\n")
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
