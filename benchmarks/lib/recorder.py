"""Reads what the timed path itself produced in its first steps: the batches
as they reached the device, each step's loss, and per-leaf norms of the
optimizer's first moment and of the parameters' change. The steps still go
through ``Trainer.train()`` -> ``train_epoch`` -> the loader -> the engine's
compiled window; the recorder wraps the engine's two entry points on the
instance, reads, and takes itself off again after ``steps`` steps."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.common import leaves_view


def first_moment(opt_state):
    """The optax state's first-moment tree: Adam's ``mu`` or momentum's ``trace``."""
    is_node = lambda x: hasattr(x, "mu") or hasattr(x, "trace")  # noqa: E731
    for node in jax.tree_util.tree_leaves(opt_state, is_leaf=is_node):
        if hasattr(node, "mu"):
            return node.mu
        if hasattr(node, "trace"):
            return node.trace
    raise ValueError("no first moment (mu / trace) in the optimizer state")


class FirstSteps:
    def __init__(self, trainer, ref, cfg: dict, steps: int, start_again):
        self.trainer, self.ref, self.cfg, self.steps = trainer, ref, cfg, steps
        self.start_again = start_again  # harness.make_weights' call: the laid-in weights, made once more
        self.batches: list = []
        self.metrics: list = []
        self.norms = None
        self._leaves = leaves_view(ref)
        engine = trainer.engine
        self._orig = (engine.train_steps_chained, engine.train_step)
        engine.train_steps_chained = self._chained
        engine.train_step = self._single

    def _norms(self, state):
        """The start is on the device for this call alone, between two steps
        of the warm-up, once the unit that was just dispatched has ended and
        given back its gradients and activations: with it the device holds
        less than a step does."""
        view = lambda tree: self._leaves(self.ref.from_program(tree, self.cfg), self.cfg)  # noqa: E731

        @jax.jit
        def norms(params, start, moment):
            l2 = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))  # noqa: E731
            delta = jax.tree.map(jnp.subtract, view(params), view(start))
            return jax.tree.map(l2, delta), jax.tree.map(l2, view(moment))

        jax.block_until_ready(state)  # or the start is asked for while the unit still runs: a filled chip has no room then
        return norms(state.params, self.start_again(), first_moment(state.opt_state))

    def _after(self, state):
        if sum(n for n, _ in self.metrics) >= self.steps:
            self.norms = self._norms(state)
            self.start_again = None
            engine = self.trainer.engine
            del engine.train_steps_chained, engine.train_step  # back to the class's own

    def _record(self, n, call, state, batch, *args):
        self.batches.append((n, jax.device_get(batch)))
        state, metrics = call(state, batch, *args)
        self.metrics.append((n, metrics))
        self._after(state)
        return state, metrics

    def _chained(self, state, batch, n):
        return self._record(n, self._orig[0], state, batch, n)

    def _single(self, state, batch):
        return self._record(1, self._orig[1], state, batch)

    def result(self) -> dict:
        """Host values, per step: the batches and what the program made of them."""
        if self.norms is None:
            raise RuntimeError(f"the first slice took fewer than {self.steps} steps")
        steps, losses = [], []
        for (n, batch), (_, metrics) in zip(self.batches, self.metrics, strict=True):
            loss = jax.device_get(metrics["loss"])
            for i in range(n):
                steps.append({k: (v[i] if n > 1 else v) for k, v in batch.items() if k in ("image", "label")})
                losses.append(float(loss[i] if n > 1 else loss))
        delta, moment = jax.device_get(self.norms)
        return {
            "batches": steps,
            "losses": losses,
            "delta": {k: float(v) for k, v in delta.items()},
            "moment": {k: float(v) for k, v in moment.items()},
        }
