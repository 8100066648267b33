"""Pallas TPU kernels for the compute hot-spots.

  * ``aggregate``  — weighted model aggregation (FedLEO eqs. 4/9): the FL
    server hot-spot, a memory-bound streaming reduction over K stacked
    parameter vectors.
  * ``flash``      — GQA flash attention (causal / sliding-window) for the
    transformer architectures.
  * ``ssd``        — Mamba2 SSD chunked scan.

Each kernel ships as <name>.py (pl.pallas_call + BlockSpec), <name>_ops.py
(jit'd wrapper that picks the execution mode with ``interpret_mode``) and
<name>_ref.py (pure-jnp oracle used by the allclose test sweeps).
"""
import jax


def interpret_mode() -> bool:
    """Whether the kernels run in Pallas interpret mode on this backend.

    Mosaic-compiled on ``tpu``; interpreted on ``cpu``, where the tests
    run.  Any other backend raises: a kernel never silently interprets
    on a device it was not written for.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on tpu or interpreted on cpu; "
        f"the default backend is {backend!r}"
    )


from repro.kernels import aggregate_ops, flash_ops, ssd_ops  # noqa: E402

__all__ = ["aggregate_ops", "flash_ops", "interpret_mode", "ssd_ops"]
