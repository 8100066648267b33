"""The FLOP counts the per-layer metrics divide by, against hand counts."""
import pytest

from bench import cell as cells
from bench.cell import BENCH, Counters, RunView, Window


def _config(name):
    path = BENCH / "configs" / f"{name}.json"
    return cells.load_json(path), cells.load_module(path.with_suffix(".py"))


def test_cnn_forward_flops_count_every_tap():
    cfg, mod = _config("cnn-mnist")
    conv1 = 2 * 28 * 28 * 32 * 1 * 9          # 451,584
    conv2 = 2 * 14 * 14 * 64 * 32 * 9         # 7,225,344
    fc1 = 2 * 7 * 7 * 64 * 128                # 802,816
    fc2 = 2 * 128 * 10                        # 2,560
    assert mod.forward_flops_per_sample(cfg) == conv1 + conv2 + fc1 + fc2 \
        == 8_482_304


def test_unet_forward_flops_count_every_tap():
    cfg, mod = _config("unet-deepglobe")
    assert cfg["input_shape"] == [128, 128, 3] and cfg["depth"] == 3
    enc = (2 * 128 * 128 * 9 * (3 * 16 + 16 * 16)          # 128x128
           + 2 * 64 * 64 * 9 * (16 * 32 + 32 * 32)         # 64x64
           + 2 * 32 * 32 * 9 * (32 * 64 + 64 * 64))        # 32x32
    bottleneck = 2 * 16 * 16 * 9 * 64 * 128
    # each input pixel meets each of a 2x2 transposed kernel's 4 taps once
    up = (2 * 16 * 16 * 4 * 128 * 64 + 2 * 32 * 32 * 9 * 128 * 64
          + 2 * 32 * 32 * 4 * 64 * 32 + 2 * 64 * 64 * 9 * 64 * 32
          + 2 * 64 * 64 * 4 * 32 * 16 + 2 * 128 * 128 * 9 * 32 * 16)
    head = 2 * 128 * 128 * 16 * 2
    assert mod.forward_flops_per_sample(cfg) == enc + bottleneck + up + head \
        == 858_259_456


@pytest.mark.parametrize("name,count", [("cnn-mnist", 421_642),
                                        ("unet-deepglobe", 285_970)])
def test_param_count_matches_config(name, count):
    import jax

    cfg, mod = _config(name)
    params = jax.eval_shape(lambda k: mod.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == cfg["num_params"] == count


def _view(trace, samples=0, wall=1.0, rounds=1):
    c = Counters()
    c.train_samples = samples
    return RunView(trace=trace, window=Window(rounds, wall, 0), counters=c,
                   peak={"bf16_flops_per_s": 100e12},
                   flops_per_sample=1_000_000)


def _metric(name):
    return cells.load_module(BENCH / "metrics" / f"{name}.py")


def test_step_mfu_is_three_forward_passes_over_train_device_time():
    # 1e6 samples x 3 x 1 MFLOP over 2 s of local-train programs (two
    # calls, one of them partly outside the window's span and still
    # counted from its start) = 1.5 TFLOP/s of a 100 TFLOP/s peak; the
    # evaluation program is not counted
    trace = {"device": [["XLA Modules", "jit__local_train_one(7)", 0, 10**9],
                        ["XLA Modules", "jit__local_train_one(7)",
                         4 * 10**9, 10**9],
                        ["XLA Modules", "jit__eval(3)", 2 * 10**9, 10**9]],
             "host": [["bench.window", 0, 4_500_000_000]]}
    got = _metric("step_mfu").read(_view(trace, samples=1_000_000, wall=5.0))
    assert abs(got - 1.5) < 1e-12


def test_step_mfu_silent_without_train_events():
    trace = {"device": [["XLA Modules", "jit__eval(3)", 0, 10**9]],
             "host": [["bench.window", 0, 10**9]]}
    assert _metric("step_mfu").read(_view(trace, samples=1000)) is None
