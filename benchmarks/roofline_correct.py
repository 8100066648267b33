import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# Depth-extrapolation correction for the roofline terms.
#
# XLA's HloCostAnalysis counts a while-loop (lax.scan) body ONCE, so the
# dry-run's cost_analysis under-reports flops/bytes for scanned-layer
# models by ~num_layers x (verified empirically; see EXPERIMENTS.md
# §Dry-run).  This tool lowers each (arch x shape) twice more with the
# layer stack UNROLLED at two shallow depths and linearly extrapolates
# every cost term to the real depth:
#
#   cost(L) = cost_outer + units(L) * cost_per_unit
#   cost_per_unit = (cost(d2) - cost(d1)) / (units(d2) - units(d1))
#
# Collective bytes parsed from the HLO get the same correction (the
# layer-body collectives are likewise counted once inside the loop).
#
# Usage: python -m benchmarks.roofline_correct --out benchmarks/dryrun_corrected.jsonl
import argparse
import dataclasses
import json
import traceback

import jax

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.configs.base import EncoderConfig
from repro.launch.dryrun import collective_bytes, lower_pair
from repro.launch.mesh import make_production_mesh


def variant_plan(arch: str):
    """Returns (cfg_small, cfg_big, units_small, units_big, units_real)."""
    cfg = get_config(arch)
    if cfg.family == "hybrid":
        g = cfg.hybrid_attn_every
        unit = g                      # one unit = g mamba layers + 1 attn use
        l1, l2 = g, 2 * g
        units_real = cfg.num_layers / g
    elif cfg.moe is not None:
        unit = cfg.moe_every
        l1, l2 = unit, 2 * unit
        units_real = cfg.num_layers / unit
    else:
        unit = 1
        l1, l2 = 2, 4
        units_real = float(cfg.num_layers)

    def make(lyrs):
        kw = dict(num_layers=lyrs, scan_layers=False)
        if cfg.encoder is not None:
            kw["encoder"] = EncoderConfig(
                num_layers=lyrs,
                max_source_len=cfg.encoder.max_source_len,
            )
        return dataclasses.replace(cfg, **kw)

    if cfg.encoder is not None:
        # enc+dec both scale: one "unit" = one enc layer + one dec layer
        units_real = float(cfg.num_layers)   # = encoder layers too
    return make(l1), make(l2), l1 / unit, l2 / unit, units_real


def measure(arch, shape_name, cfg, mesh, sharding_mode="fsdp2d"):
    lowered, _ = lower_pair(arch, shape_name, mesh, cfg=cfg,
                            sharding_mode=sharding_mode)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
    }


def extrapolate(m1, m2, u1, u2, u_real):
    out = {}
    for key in ("flops", "bytes"):
        slope = (m2[key] - m1[key]) / (u2 - u1)
        outer = m1[key] - u1 * slope
        out[key] = max(0.0, outer + u_real * slope)
    coll = {}
    kinds = set(m1["coll"]) | set(m2["coll"])
    for kind in kinds:
        a, b = m1["coll"].get(kind, 0.0), m2["coll"].get(kind, 0.0)
        slope = (b - a) / (u2 - u1)
        outer = a - u1 * slope
        coll[kind] = max(0.0, outer + u_real * slope)
    out["coll"] = coll
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS) + ["all"],
                    default="all")
    ap.add_argument("--shape", choices=list(INPUT_SHAPES) + ["all"],
                    default="all")
    ap.add_argument("--out",
                    default="benchmarks/dryrun_corrected.jsonl")
    ap.add_argument("--sharding", choices=["fsdp2d", "zero1"],
                    default="fsdp2d")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    mesh = make_production_mesh(multi_pod=False)

    done = set()
    if args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("ok"):
                        done.add((r["arch"], r["shape"]))
                except Exception:
                    pass

    for arch in archs:
        cfg_small, cfg_big, u1, u2, u_real = variant_plan(arch)
        for shape in shapes:
            if (arch, shape) in done:
                print(f"[correct] skip {arch} x {shape}")
                continue
            try:
                m1 = measure(arch, shape, cfg_small, mesh, args.sharding)
                m2 = measure(arch, shape, cfg_big, mesh, args.sharding)
                ex = extrapolate(m1, m2, u1, u2, u_real)
                rec = {
                    "ok": True, "arch": arch, "shape": shape,
                    "mesh": "16x16", "corrected": True,
                    "sharding": args.sharding,
                    "flops": ex["flops"], "bytes_accessed": ex["bytes"],
                    "collective_bytes": ex["coll"],
                    "raw_small": m1, "raw_big": m2,
                    "units": [u1, u2, u_real],
                }
                print(f"[correct] {arch} x {shape}: "
                      f"flops {m1['flops']:.3e}/{m2['flops']:.3e} -> "
                      f"{ex['flops']:.3e} (x{u_real:.0f} units)")
            except Exception as e:
                traceback.print_exc()
                rec = {"ok": False, "arch": arch, "shape": shape,
                       "error": f"{type(e).__name__}: {e}"}
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
