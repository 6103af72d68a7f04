"""The housing-MLP family: the program's model built from a configuration.

The learners run the program's own model code (``repro.models.mlp``) under
the program's ``Learner``, with the loss and evaluation functions that
``repro.launch.train.build_housing_learners`` gives them; the evaluation is
jitted, as the learner's own step is.  Only the data and the initial weights
come from the benchmark.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, spec

learner_flops = counts.mlp_learner_flops

#: A CPU test's stand-in of a configuration of this family: the keys it shrinks
#: in the configuration, the traffic and the traffic's federation.
TINY = {"config": dict(n_hidden_layers=4, width=32)}


def program_model(config: dict):
    from repro.configs.housing_mlp import MLPConfig

    return MLPConfig(
        name=config["name"],
        n_hidden_layers=int(config["n_hidden_layers"]),
        width=int(config["width"]),
        n_features=int(config["n_features"]),
        n_outputs=int(config["n_outputs"]),
    )


def abstract_params(model):
    from repro.models import mlp

    return jax.eval_shape(lambda: mlp.init_params(jax.random.key(0), model))


def learner_fns(model):
    """``(loss_fn, eval_fn)`` as the housing launcher builds them, eval jitted."""
    from repro.models import mlp

    return mlp.mse_loss, jax.jit(lambda p, b: {"eval_loss": mlp.mse_loss(p, b)})


def init_leaf(path: str, key, shape, config: dict):
    """He-normal weights (a 100-layer ReLU stack keeps its signal), zero biases."""
    if path.endswith("['b']"):
        return jnp.zeros(shape, jnp.float32)
    return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(2.0 / shape[0])


@functools.cache
def _replay(lr: float):
    """The plain federation's first rounds in one call: the largest loss a learner meets."""
    ref = spec.reference("mlp")

    def loss(p, x, y):
        return ref.loss(p, (x, y), {})

    step = jax.value_and_grad(loss)

    def learner(g, batches):
        def one(p, b):
            val, grad = step(p, *b)
            return jax.tree_util.tree_map(lambda w, d: w - lr * d, p, grad), val

        return jax.lax.scan(one, g, batches)

    def fedavg_round(g, batches):
        def add(carry, b):
            total, worst = carry
            p, vals = learner(g, b)
            total = jax.tree_util.tree_map(lambda t, w, w0: t + (w - w0), total, p, g)
            return (total, jnp.maximum(worst, jnp.max(vals))), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, g)
        (total, worst), _ = jax.lax.scan(add, (zeros, jnp.float32(-jnp.inf)), batches)
        n = batches[0].shape[0]
        return jax.tree_util.tree_map(lambda w0, t: w0 + t / n, g, total), worst

    @jax.jit
    def run(params, xs, ys):
        _, worst = jax.lax.scan(fedavg_round, params, (xs, ys))
        return jnp.max(worst)

    return run


def usable(params, config: dict, traffic: dict, source) -> bool:
    """A draw on which the plain federation's losses stay near the data's scale.

    Through 100 He-normal layers of width 320 a few draws in a hundred train
    stably for a round or two and then blow up: a learner's loss reads
    thousands and the round after NaN.  The plain reference replays the
    first ``init_check_rounds`` rounds of the traffic's federation (equal
    weights) on the learners' own batches, and the draw is refused when a
    loss exceeds ``init_max_loss`` or is not finite.
    """
    n = int(traffic["learners"])
    fed = spec.federation(traffic)
    size, steps = int(fed["batch_size"]), int(fed["local_steps"])
    rounds = int(config["init_check_rounds"])
    rows = [[[source.batch(i, r * steps + k, size) for k in range(steps)] for i in range(n)]
            for r in range(rounds)]
    xs = np.asarray([[[b[0] for b in learner] for learner in rnd] for rnd in rows])
    ys = np.asarray([[[b[1] for b in learner] for learner in rnd] for rnd in rows])
    worst = float(_replay(float(config["learning_rate"]))(params, xs, ys))
    return worst <= float(config["init_max_loss"])
