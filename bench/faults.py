"""Faults planted under the timed path, to show that ``correct`` catches
them: the calibration reads them on the chip, the tests on the CPU.

  state_unchanged: local training hands back the model it was given.
  half_batch:      every loss the program computes, inside its compiled
                   local-train step and its evaluation, is the mean over
                   the first half of the batch only.
  worse_schedule:  the strategy runs with its reference module's
                   ``SCHEDULE_FAULT`` arguments, a schedule that ends rounds
                   later.
"""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

from bench import cell as cells


def _state_unchanged(params, n):
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (n,) + p.shape), params)


def _first_half(loss_fn):
    def loss(logits, labels):
        half = max(1, logits.shape[0] // 2)
        return loss_fn(logits[:half], labels[:half])

    return loss


def task_class(fault: str, base=None):
    """The benchmark's task class (or ``base``) with ``fault`` planted in
    local training."""
    base = base or cells.traced_task_class()

    class Faulty(base):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            if fault == "half_batch":      # before the first trace
                self.loss_fn = _first_half(self.loss_fn)

        def local_train(self, params, client_ids, rng):
            out = super().local_train(params, client_ids, rng)
            if fault == "state_unchanged":
                return _state_unchanged(params, len(list(client_ids)))
            return out

    return Faulty


def worse_schedule(cell: cells.Cell) -> cells.Cell:
    """A copy of ``cell`` whose strategy schedules worse."""
    cell = copy.copy(cell)
    cell.traffic = dict(cell.traffic,
                        strategy_args=cell.strategy_ref.SCHEDULE_FAULT)
    return cell


FAULTS = ("state_unchanged", "half_batch")
