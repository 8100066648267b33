"""FederatedTask: the learning substrate plugged into the FL engines.

Wraps a model (init/apply), an optimizer, and client datasets into
jit/vmap-compiled local-training and evaluation functions:

  * ``local_train(params, client_ids)``: vmapped I-epoch mini-batch SGD
    on every listed client *in parallel* (stacked params) — the JAX
    realization of "multiple concurrent training processes" (§IV-A).
  * ``evaluate(params)``: global-model metrics on a held-out test set.
  * ``train_time_s(client)``: eq. (11) wall-clock model
    t_train = I * n_k * b_k * c_k / f_k  (simulated clock, Table I).
  * ``payload_bits``: z|N| for the comm model.

The task is model-agnostic: classification (CNN), segmentation (U-Net)
and LM (assigned architectures) tasks all fit.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compute.fleet import FleetComputeModel
from repro.data.partition import ClientData, stack_client_arrays
from repro.data.synthetic import Dataset
from repro.models import nn
from repro.obs import span
from repro.optim import Optimizer
from repro.optim.optimizers import apply_updates

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainHyperparams:
    """Paper Table I (lower part) defaults."""

    local_epochs: int = 100          # I
    learning_rate: float = 0.001     # eta
    batch_size: int = 32             # b_k
    cycles_per_sample: float = 1.0e3  # c_k
    cpu_freq_hz: float = 1.0e9       # f_k
    bits_per_param: int = 32         # z


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean softmax cross-entropy; supports (B, C) or (B, H, W, C) logits."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logp.dtype)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def _epoch_batches(m: int, batch_size: int) -> Tuple[int, int]:
    """(n_batches, batch_size) of one epoch over m samples; tiny clients
    (m < b_k) take full-batch steps."""
    bsz = min(batch_size, max(1, m))
    return max(1, m // bsz), bsz


def accuracy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))


class FederatedTask:
    def __init__(
        self,
        *,
        init_fn: Callable[..., PyTree],
        apply_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
        clients: Sequence[ClientData],
        test_set: Dataset,
        optimizer: Optimizer,
        hp: TrainHyperparams = TrainHyperparams(),
        loss_fn: Callable = cross_entropy_loss,
        rng: Optional[jax.Array] = None,
        sim_epochs: Optional[int] = None,
        payload_bits_override: Optional[int] = None,
        compute: Optional[FleetComputeModel] = None,
    ):
        """Args:
          sim_epochs: epochs actually executed on this host (defaults to
            hp.local_epochs). The *simulated clock* always charges
            hp.local_epochs via eq. (11); running fewer real epochs keeps
            CPU benchmarks tractable without changing timing fidelity.
          payload_bits_override: charge the comm model for this payload
            size z|N| instead of the proxy model's true size — used to
            simulate the paper's full-size CNN/U-Net (or a 100M+ LM)
            while training a reduced proxy on CPU.
          compute: heterogeneous fleet compute model (repro.compute) —
            ``train_time_s`` consults it per client before falling back
            to the uniform eq. (11) c_k/f_k constant.  None (default)
            keeps the paper's uniform fleet; ``FLStrategy`` also
            resolves one from ``SimConfig.compute`` without mutating
            the task, so one task can be shared across arms.
        """
        self.apply_fn = apply_fn
        self.clients = list(clients)
        self.test_set = test_set
        self.optimizer = optimizer
        self.hp = hp
        self.loss_fn = loss_fn
        self.compute = compute
        self.sim_epochs = sim_epochs if sim_epochs is not None else hp.local_epochs
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.global_params = init_fn(rng)
        # `is None`, not `or`: an explicit 0-bit override must not fall
        # back to the proxy model's true size
        self._payload_bits = (
            payload_bits_override
            if payload_bits_override is not None
            else nn.param_bits(self.global_params, hp.bits_per_param)
        )

        # stacked per-client data for vmapped local training
        self._x_stack, self._y_stack, self._counts = stack_client_arrays(
            self.clients
        )
        self._x_stack = jnp.asarray(self._x_stack)
        self._y_stack = jnp.asarray(self._y_stack)

        self._local_train_vmapped = jax.jit(
            jax.vmap(self._local_train_one, in_axes=(0, 0, 0, 0))
        )
        self._eval_jit = jax.jit(self._eval)

    # --- payload & timing ------------------------------------------------------
    @property
    def payload_bits(self) -> int:
        return self._payload_bits

    def num_samples(self, client_id: int) -> int:      # m_k
        return int(self._counts[client_id])

    def executed_batches(self, client_id: int) -> Tuple[int, int]:
        """(n_batches, batch_size) as ``_local_train_one`` executes
        them on this client's own samples: tiny clients (m < b_k) fall
        back to full-batch steps, so the simulated clock must charge the
        samples actually processed — not b_k.  For m >= b_k this is
        exactly eq. (11)'s (m // b_k, b_k)."""
        return _epoch_batches(self.num_samples(client_id), self.hp.batch_size)

    def train_time_s(self, client_id: int) -> float:
        """Eq. (11): t_train(k) = I * n_k * b_k * c_k / f_k, charged
        for the batches actually executed.  With a fleet compute model
        attached, c_k / f_k is replaced by the client satellite's
        roofline per-sample cost (degenerate tiers fall through to the
        uniform constant)."""
        hp = self.hp
        n_batches, bsz = self.executed_batches(client_id)
        if self.compute is not None:
            c = self.clients[client_id]
            t = self.compute.train_time_s(
                c.plane, c.slot, local_epochs=hp.local_epochs,
                n_batches=n_batches, batch_size=bsz,
            )
            if t is not None:
                return t
        return (
            hp.local_epochs * n_batches * bsz * hp.cycles_per_sample
        ) / hp.cpu_freq_hz

    # --- local training ---------------------------------------------------------
    def _local_train_one(
        self, params: PyTree, x: jax.Array, y: jax.Array, rng: jax.Array
    ) -> PyTree:
        """I epochs of mini-batch SGD on one client (runs under vmap)."""
        m = x.shape[0]
        n_batches, bsz = _epoch_batches(m, self.hp.batch_size)
        opt_state = self.optimizer.init(params)

        def loss(p: PyTree, xb: jax.Array, yb: jax.Array) -> jax.Array:
            return self.loss_fn(self.apply_fn(p, xb), yb)

        Carry = Tuple[PyTree, PyTree]

        def epoch_body(carry: Carry, ekey: jax.Array) -> Tuple[Carry, None]:
            params, opt_state = carry
            perm = jax.random.permutation(ekey, m)

            def batch_body(carry: Carry, i: jax.Array) -> Tuple[Carry, None]:
                params, opt_state = carry
                idx = jax.lax.dynamic_slice_in_dim(
                    perm, i * bsz, bsz
                )
                g = jax.grad(loss)(params, x[idx], y[idx])
                updates, opt_state = self.optimizer.update(g, opt_state, params)
                return (apply_updates(params, updates), opt_state), None

            (params, opt_state), _ = jax.lax.scan(
                batch_body, (params, opt_state), jnp.arange(n_batches)
            )
            return (params, opt_state), None

        ekeys = jax.random.split(rng, self.sim_epochs)
        (params, _), _ = jax.lax.scan(epoch_body, (params, opt_state), ekeys)
        return params

    def local_train(
        self, params: PyTree, client_ids: Sequence[int], rng: jax.Array
    ) -> PyTree:
        """Train the given global params on each listed client in parallel.

        Returns stacked params with leading axis len(client_ids).  Runs
        in a ``repro.local_train`` profiler span that carries the call's
        sequential SGD ``steps`` and the ``samples`` trained over all
        listed clients.
        """
        ids = np.asarray(list(client_ids))
        # every client trains on the stack's padded length: the steps and
        # batch size of the largest client, for each client of the call
        n_batches, bsz = _epoch_batches(self._x_stack.shape[1],
                                        self.hp.batch_size)
        steps = self.sim_epochs * n_batches
        with span("local_train", steps=steps, samples=len(ids) * steps * bsz):
            stacked = jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p, (len(ids),) + p.shape), params
            )
            rngs = jax.random.split(rng, len(ids))
            return self._local_train_vmapped(
                stacked, self._x_stack[ids], self._y_stack[ids], rngs
            )

    # --- evaluation ---------------------------------------------------------------
    def _eval(
        self, params: PyTree, x: jax.Array, y: jax.Array
    ) -> Dict[str, jax.Array]:
        logits = self.apply_fn(params, x)
        return {
            "loss": self.loss_fn(logits, y),
            "accuracy": accuracy(logits, y),
        }

    def evaluate(self, params: PyTree, max_samples: int = 1024) -> Dict[str, float]:
        """Loss and accuracy of ``params`` on the test set, in a
        ``repro.evaluate`` profiler span; ``repro.wait`` inside it is
        the host blocked on the device for the numbers."""
        with span("evaluate"):
            x = jnp.asarray(self.test_set.x[:max_samples])
            y = jnp.asarray(self.test_set.y[:max_samples])
            out = self._eval_jit(params, x, y)
            with span("wait"):
                return {k: float(v) for k, v in out.items()}

    # --- client lookup ---------------------------------------------------------------
    def clients_on_plane(self, plane: int) -> List[int]:
        return [i for i, c in enumerate(self.clients) if c.plane == plane]

    def client_histograms(self) -> np.ndarray:
        return np.stack([c.histogram for c in self.clients])
