"""The dense decoder family (GQA, QKV bias, RoPE, SwiGLU, tied embeddings).

A configuration in Hugging Face's keys is mapped onto the program's
``ModelConfig`` and run through ``repro.models.transformer.lm_loss`` under
the program's ``Learner``, as ``repro.launch.train.build_lm_learners``
builds its learners.  Only the data and the initial weights come from the
benchmark.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import counts

learner_flops = counts.lm_learner_flops

#: A CPU test's stand-in of a configuration of this family: the keys it
#: shrinks in the configuration, the traffic and the traffic's federation.
TINY = {
    "config": dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                   num_key_value_heads=2, num_hidden_layers=2, vocab_size=512),
    "traffic": dict(seq_len=32),
    "federation": dict(local_steps=2),
}


def program_model(config: dict):
    from repro.models.config import ModelConfig

    heads = int(config["num_attention_heads"])
    return ModelConfig(
        name=config["name"],
        arch_type="dense",
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=heads,
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["hidden_size"]) // heads,
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        qkv_bias=True,
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        # 151,936 is a multiple of 128 already: no padded vocabulary rows.
        vocab_pad_to=int(config["program"]["vocab_pad_to"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["param_dtype"]),
        source=config["source"],
    )


def abstract_params(model):
    from repro.models import transformer

    return transformer.abstract_params(model)


def learner_fns(model):
    """``(loss_fn, eval_fn)`` as ``build_lm_learners`` builds them, eval jitted.

    Unjitted, the evaluation's layer scan compiles anew on every call.
    """
    from repro.models import transformer

    def loss_fn(params, batch):
        return transformer.lm_loss(params, batch, model)

    @jax.jit
    def eval_fn(params, batch):
        return {"eval_loss": transformer.lm_loss(params, batch, model)}

    return loss_fn, eval_fn


def init_leaf(path: str, key, shape, config: dict):
    """Hugging Face's initialisation: N(0, initializer_range), ones, zero biases."""
    name = path.rsplit("[", 1)[-1].strip("]'")
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if name in ("bq", "bk", "bv"):
        return jnp.zeros(shape, jnp.float32)
    return jax.random.normal(key, shape, jnp.float32) * float(config["initializer_range"])
