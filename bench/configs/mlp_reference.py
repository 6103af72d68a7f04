"""Plain reference of the housing MLP: ReLU layers and a mean squared error.

Written from the paper's description (MetisFL section 4.2: 100 dense hidden
layers of constant width, a scalar regression head, vanilla SGD) in plain
``jax.numpy``; it imports nothing of the program.  The parameters come in
the wire layout ``{"layers": [{"w", "b"}, ...], "out": {"w", "b"}}``.
``dtype`` is the precision the reference computes and keeps its state in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def loss(params, batch, config: dict, dtype=jnp.float32):
    x, y = batch
    h = jnp.asarray(x, dtype)
    for layer in params["layers"]:
        h = jax.nn.relu(h @ layer["w"].astype(dtype) + layer["b"].astype(dtype))
    pred = h @ params["out"]["w"].astype(dtype) + params["out"]["b"].astype(dtype)
    err = pred.astype(jnp.float32) - jnp.asarray(y, jnp.float32)
    return jnp.mean(err * err)
