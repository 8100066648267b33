"""Chip smoke test: two full-width FedLEO rounds on one TPU.

Runs the system's main path once through its normal entry points
(``SimConfig``, ``FederatedTask``, ``FedLEO``): the paper-5x8
constellation with the Rolla station, the paper's deep CNN at full width
(widths (32, 64), hidden 128: 421,642 params), 60,000 MNIST-like
training samples split non-IID over the 40 satellites, a 10,000-sample
test set, Table I hyperparameters with batch 32, and the Pallas
aggregation kernel at the sink and the ground station.  All data and
weights are made from ``--seed``.  The checks are those of
``repro.launch.smoke.run_fedleo_smoke``.

    python chip_smoke.py [--seed N]

Run it from a checkout of the repository with ``JAX_PLATFORMS`` unset.
It exits non-zero, with no result line, when JAX finds no TPU, when the
repository's sources are missing, or when a check fails.  On success its
last line is ``{"ok": true, "device": {...}}``.  The persistent compile
cache goes to ``$JAX_COMPILATION_CACHE_DIR`` if set, else
``<repo>/.jax_cache``.
"""
import argparse
import json
import pathlib
import sys

TRAIN_SAMPLES = 60_000
TEST_SAMPLES = 10_000
CNN_WIDTHS = (32, 64)
CNN_HIDDEN = 128
ROUNDS = 2
SIM_EPOCHS = 100        # Table I's I


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"no repository sources at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"no TPU: JAX's first device is {device.platform} "
              f"({device.device_kind})", file=sys.stderr)
        return 1
    print(f"device: {device.device_kind} x {len(devices)} "
          f"({device.platform})")

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.smoke import run_fedleo_smoke

    print(f"compile cache: {enable_compile_cache()}")
    run_fedleo_smoke(
        train_samples=TRAIN_SAMPLES,
        test_samples=TEST_SAMPLES,
        widths=CNN_WIDTHS,
        hidden=CNN_HIDDEN,
        rounds=ROUNDS,
        sim_epochs=SIM_EPOCHS,
        seed=args.seed,
    )
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
