"""jit'd wrapper for the SSD kernel (Mosaic on TPU, interpret on CPU).

Returns (y, final_state) to match the model's ssd_chunked signature; the
kernel itself produces y, and the final state (needed only when chaining
prefill -> decode) is recovered with one extra lightweight jnp pass.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels import ssd as _ssd
from repro.models.mamba2 import ssd_chunked


def ssd(x, dt, A, Bm, Cm, chunk: int = 128) -> Tuple[jnp.ndarray, jnp.ndarray]:
    s = x.shape[1]
    chunk = min(chunk, s)
    if s % chunk != 0:
        raise ValueError(
            f"the SSD kernel needs a sequence length divisible by the "
            f"chunk ({chunk}), got {s}; use ssd_impl='xla' for ragged "
            f"lengths"
        )
    y = _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                      interpret=interpret_mode())
    # final state via the jnp chunk recurrence (cheap relative to y)
    _, final_state = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    return y, final_state
