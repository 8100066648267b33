"""Launch-layer integration: lower+compile on a small fake-device mesh.

Runs in a SUBPROCESS because the forced host-device count must be set
before jax initializes (the main test process keeps 1 device, per the
dry-run isolation rule).  Uses smoke configs so the whole thing takes
seconds; the full 40-pair × 2-mesh sweep artifacts live in
dryrun_single_pod.jsonl / dryrun_multi_pod.jsonl.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from repro.configs import get_smoke_config
from jax.sharding import AxisType
from repro.launch.dryrun import collective_bytes, lower_pair

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
out = {}
for arch, shape in [("gemma-7b", "train_4k"),
                    ("kimi-k2-1t-a32b", "train_4k"),
                    ("mamba2-780m", "decode_32k")]:
    import dataclasses
    from repro.configs.base import INPUT_SHAPES
    cfg = get_smoke_config(arch)
    # shrink the input shape for speed
    sh = INPUT_SHAPES[shape]
    INPUT_SHAPES[shape] = dataclasses.replace(sh, seq_len=256,
                                              global_batch=8)
    try:
        lowered, meta = lower_pair(arch, shape, mesh, cfg=cfg)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
        out[f"{arch}|{shape}"] = {
            "ok": True,
            "flops": float(cost.get("flops", -1)),
            "collectives": {k: float(v) for k, v in coll.items()},
        }
    finally:
        INPUT_SHAPES[shape] = sh
print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dryrun_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # forced host-device count only multiplies the CPU platform; pin it
    # so jax never probes a (baked-in but absent) TPU backend for 60 s
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=540, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout[-2000:]
    return json.loads(line[0][len("RESULT:"):])


def test_lower_compile_on_multipod_mesh(dryrun_result):
    assert len(dryrun_result) == 3
    for key, rec in dryrun_result.items():
        assert rec["ok"], key
        assert rec["flops"] > 0, key


def test_train_step_has_gradient_collectives(dryrun_result):
    rec = dryrun_result["gemma-7b|train_4k"]
    # data-parallel gradient sync must appear as collective traffic
    assert sum(rec["collectives"].values()) > 0


def test_moe_dispatch_lowered(dryrun_result):
    assert dryrun_result["kimi-k2-1t-a32b|train_4k"]["ok"]


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
      %ag = bf16[8,128]{1,0} all-gather(%x), dimensions={0}
      %ar.1 = f32[64]{0} all-reduce(%y), to_apply=%add
      %a2a = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(%p, %q)
      %done = f32[64]{0} all-reduce-done(%ar.1)
    """
    out = collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2
    assert out["all-reduce"] == 64 * 4
    assert out["all-to-all"] == 2 * 16 * 4
