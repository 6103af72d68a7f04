"""Plain reference of a Qwen2-style dense decoder and its causal LM loss.

Written from the Qwen2 report (arXiv:2407.10671) and its Hugging Face
config in plain ``jax.numpy``; it imports nothing of the program.  Each
layer: RMSNorm (eps 1e-6, scale) -> GQA self-attention with QKV bias and
rotary embeddings (rotate-half, base ``rope_theta``) under a causal mask ->
residual; RMSNorm -> SwiGLU MLP -> residual.  A final RMSNorm, logits
against the tied embedding, and the mean next-token cross-entropy.

The parameters come in the wire layout: ``embed``, ``final_norm`` and
``segments``, a list of scanned segments, each a tuple of unit layers whose
leaves are stacked over the segment's repeats.  ``dtype`` is the precision
the reference computes and keeps its state in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6


def _rms(x, scale):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + EPS)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: (B, S, H, hd); rotate-half rotary embedding at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _layers(params):
    for seg in params["segments"]:
        repeats = jax.tree_util.tree_leaves(seg)[0].shape[0]
        for r in range(repeats):
            for unit in seg:
                yield jax.tree_util.tree_map(lambda a, r=r: a[r], unit)


def loss(params, batch, config: dict, dtype=jnp.float32):
    heads = int(config["num_attention_heads"])
    kv_heads = int(config["num_key_value_heads"])
    hd = int(config["hidden_size"]) // heads
    theta = float(config["rope_theta"])
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    embed = params["embed"].astype(dtype)
    x = embed[tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for p in _layers(params):
        a = p["attn"]
        h = _rms(x, p["norm1"]["scale"])
        q = (h @ a["wq"].astype(dtype) + a["bq"].astype(dtype)).reshape(b, s, heads, hd)
        k = (h @ a["wk"].astype(dtype) + a["bk"].astype(dtype)).reshape(b, s, kv_heads, hd)
        v = (h @ a["wv"].astype(dtype) + a["bv"].astype(dtype)).reshape(b, s, kv_heads, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=2)
        v = jnp.repeat(v, heads // kv_heads, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(hd)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, heads * hd)
        x = x + att @ a["wo"].astype(dtype)
        m = p["mlp"]
        h = _rms(x, p["norm2"]["scale"])
        gate = jax.nn.silu(h @ m["w_gate"].astype(dtype))
        x = x + (gate * (h @ m["w_up"].astype(dtype))) @ m["w_down"].astype(dtype)
    x = _rms(x, params["final_norm"]["scale"])
    logits = (x @ embed.T).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
