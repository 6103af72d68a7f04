"""A plain federation, written without the program.

The traffic file's ``federation`` settings name its parts, each a file of
its own that the reference finds by name, as the program's
``FederationEnv`` takes them:

* ``protocol`` -> ``bench/protocols/<name>.py``: the round loop (which
  learners start from which model, and when a model is committed);
* ``upload_codec`` -> ``bench/codecs/<name>.py``: what a learner's flat row
  becomes on the uplink;
* ``aggregation_rule`` -> ``bench/rules/<name>.py``: the reduction of the
  round's rows into the aggregated model;
* ``server_optimizer`` -> ``bench/servers/<name>.py``: the server step from
  the aggregated model to the committed one.

A learner's local step is plain SGD on the family's plain loss
(``bench/configs/<family>_reference.py``) over the same batches the
program's learners drew; its parameters flatten in pytree-leaf order.  The
reference imports nothing of the program and takes nothing the program
made: the weights are regenerated from the seed.

``dtype`` is the precision the reference keeps its state in and computes in,
its matmuls at the configuration's ``matmul_precision``; ``uplink`` replaces
the codec's ``transmit`` and ``fold`` the rule's ``Fold`` (the control and
the diagnostics of ``bench/calibrate.py``).  ``fault`` plants one fault in
the reference put in the program's place: ``"half_batch"`` (each step sees
the first half of its batch, the mean taken over it) or
``"upload_altered"`` (learner 0's first uploaded value is off by 1.0).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench import spec


@dataclasses.dataclass
class Readings:
    """What a federation's first rounds give the comparison.

    ``losses[r][i]``: learner ``i``'s training loss at its last local step of
    round ``r``; ``changes[r]``: per leaf, the norm of the global model's
    change after rounds ``0..r``.
    """

    losses: list[list[float]]
    changes: list[dict[str, float]]


def leaf_paths(tree) -> list[str]:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _leaf_norms(tree, base):
    return [jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)).reshape(-1))
            for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(base))]


def change_norms(tree, base) -> dict[str, float]:
    """Per leaf path, ``||tree - base||`` in float32."""
    norms = jax.device_get(_leaf_norms(tree, base))
    return dict(zip(leaf_paths(base), (float(n) for n in norms)))


class Plain:
    """One plain federation's parts, which a protocol's round loop drives."""

    def __init__(self, ref_mod, config: dict, traffic: dict, params0, source, *,
                 dtype=jnp.float32, uplink=None, fold=None, fault: str | None = None):
        s = self.settings = spec.federation(traffic)
        self.n = int(traffic["learners"])
        self.steps = int(s["local_steps"])
        self.batch = int(s["batch_size"])
        self.weights = [float(traffic["examples_per_learner"])] * self.n
        self.source = source
        self.fault = fault
        self.Fold = fold or spec.part("rules", s["aggregation_rule"]).Fold
        self.server = spec.part("servers", s["server_optimizer"])
        transmit = uplink or spec.part("codecs", s["upload_codec"]).transmit
        lr = float(config["learning_rate"])
        treedef = jax.tree_util.tree_structure(params0)
        shapes = [(l.shape, l.size) for l in jax.tree_util.tree_leaves(params0)]

        def loss(p, b):
            return ref_mod.loss(p, b, config, dtype)

        @jax.jit
        def sgd_step(p, b):
            val, grads = jax.value_and_grad(loss)(p, b)
            return jax.tree_util.tree_map(lambda w, g: (w - lr * g).astype(dtype), p, grads), val

        @jax.jit
        def flatten(p):
            return jnp.concatenate([l.reshape(-1) for l in jax.tree_util.tree_leaves(p)])

        # A float32 row is donated: the uplink's float32 output takes its buffer.
        @functools.partial(jax.jit, donate_argnums=0 if dtype == jnp.float32 else ())
        def send(row):
            return transmit(row).astype(jnp.float32)

        @jax.jit
        def unflatten(row):
            out, off = [], 0
            for shape, size in shapes:
                out.append(row[off:off + size].reshape(shape).astype(dtype))
                off += size
            return jax.tree_util.tree_unflatten(treedef, out)

        self.sgd_step, self.flatten, self.send, self.unflatten = sgd_step, flatten, send, unflatten
        self.start = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params0)

    def train(self, p, learner: int, first: int):
        """Local steps on batches ``first..``; the parameters and last loss."""
        val = None
        for k in range(first, first + self.steps):
            b = self.source.batch(learner, k, self.batch)
            if self.fault == "half_batch":
                b = jax.tree_util.tree_map(lambda a: a[: a.shape[0] // 2], b)
            p, val = self.sgd_step(p, b)
        return p, val

    def update(self, base, learner: int, first: int):
        """Local steps from the global row ``base`` on batches ``first..``.

        Returns the learner's flat row as the uplink delivers it, in
        float32, and its last loss.  The trained parameters are freed once
        flattened, and the flat row once sent.  The row is waited for, so
        that the host does not queue the next learner's steps, whose buffers
        the device would then hold beside this learner's.
        """
        p, val = self.train(self.unflatten(base), learner, first)
        row = self.flatten(p)
        del p
        if self.fault == "upload_altered" and learner == 0:
            row = row.at[0].add(1.0)
        return jax.block_until_ready(self.send(row)), val

    def commit(self, base, fold):
        """The committed model from the global row and the round's fold."""
        return self.unflatten(self.server.commit(base, fold.result(), self.settings))


def run(ref_mod, config: dict, traffic: dict, params0, source, rounds: int,
        **kw) -> Readings:
    with jax.default_matmul_precision(config["matmul_precision"]):
        fl = Plain(ref_mod, config, traffic, params0, source, **kw)
        protocol = spec.part("protocols", fl.settings["protocol"])
        return protocol.run(fl, rounds)
