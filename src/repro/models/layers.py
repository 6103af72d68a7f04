"""Model building blocks: norms, RoPE, attention (GQA/MLA/sliding), MLP, MoE,
Mamba2 (SSD).  Pure functions over parameter dicts; every block has an
``init_*`` (parameter construction) and an apply function.

Decode paths take and return explicit cache entries (``models/kvcache.py``
defines their layout); train/prefill paths are cache-free.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.sharding import ShardingPolicy, constrain

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def _dense_init(key, shape, param_dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(
        param_dtype
    )


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, dim: int | None = None):
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), cfg.param_dtype)}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), cfg.param_dtype)
    return p


def apply_norm(p, x: jax.Array, cfg: ModelConfig, eps: float = 1e-6) -> jax.Array:
    # f32 statistics AND f32 apply.  §Perf cycle 6 tried a bf16 apply to
    # avoid f32 residual copies — REFUTED: measured HLO bytes rose 20-40%
    # on the train shapes (the f32 path fuses into adjacent f32 consumers;
    # the bf16 path forced extra round-trips).  Kept as the measured winner.
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def _rms_head_norm(scale, x, eps: float = 1e-6):
    """qk-norm: RMS over the head dim."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary / sinusoidal position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(positions: jax.Array, d_model: int) -> jax.Array:
    """Whisper-style absolute sinusoidal embeddings, (..., S, D)."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / (half - 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# attention (GQA, sliding window, qk-norm, optional bias)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, cross: bool = False):
    D = cfg.d_model
    hd = cfg.resolved_head_dim
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 8)
    p = {
        "wq": _dense_init(ks[0], (D, H * hd), cfg.param_dtype),
        "wk": _dense_init(ks[1], (D, KVH * hd), cfg.param_dtype),
        "wv": _dense_init(ks[2], (D, KVH * hd), cfg.param_dtype),
        "wo": _dense_init(ks[3], (H * hd, D), cfg.param_dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), cfg.param_dtype)
        p["bk"] = jnp.zeros((KVH * hd,), cfg.param_dtype)
        p["bv"] = jnp.zeros((KVH * hd,), cfg.param_dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.param_dtype)
        p["k_norm"] = jnp.ones((hd,), cfg.param_dtype)
    return p


def _project_qkv(p, xq: jax.Array, xkv: jax.Array, cfg: ModelConfig):
    hd = cfg.resolved_head_dim
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    q = xq @ p["wq"].astype(xq.dtype)
    k = xkv @ p["wk"].astype(xkv.dtype)
    v = xkv @ p["wv"].astype(xkv.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(*q.shape[:-1], H, hd)
    k = k.reshape(*k.shape[:-1], KVH, hd)
    v = v.reshape(*v.shape[:-1], KVH, hd)
    if cfg.qk_norm:
        q = _rms_head_norm(p["q_norm"], q)
        k = _rms_head_norm(p["k_norm"], k)
    return q, k, v


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def _attn_mask(q_len: int, k_len: int, q_offset, mode: str, window: int):
    """(q_len, k_len) additive mask.  q_offset: scalar (decode position)."""
    qi = q_offset + jnp.arange(q_len)[:, None]
    kj = jnp.arange(k_len)[None, :]
    if mode == "full":
        return jnp.zeros((q_len, k_len), jnp.float32)
    ok = kj <= qi
    if mode == "sliding":
        ok = ok & (kj > qi - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _sdpa_naive(q, k, v, mask, policy: ShardingPolicy | None, *, head_sharded: bool,
                scale: float):
    """softmax(q k^T / sqrt(d)) v with full S^2 score materialization.

    The einsum baseline: simple, but writes (B,H,Sq,Sk) f32 scores to HBM —
    §Perf cycle 1 measures this against the chunked path.
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale + mask
    if policy is not None and policy.active:
        hspec = policy.model_axis if head_sharded else None
        sspec = None if head_sharded else policy.model_axis
        scores = constrain(scores, policy, policy.data_axes, hspec, sspec, None)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out


def _sdpa_chunked(q, k, v, policy, *, head_sharded: bool, scale: float,
                  mode: str, window: int, q_offset, chunk: int):
    """Flash-style attention: lax.scan over KV chunks with online softmax.

    No (Sq, Sk) score tensor ever reaches HBM — per step only
    (B, H, Sq, chunk).  Equivalent to the naive path to fp tolerance
    (tests/test_models.py::test_chunked_attention_matches_naive).
    """
    B, Sq, H, hd = q.shape
    hd_v = v.shape[-1]  # may differ from hd (MLA: qk=[nope;rope], v=v_head_dim)
    Sk = k.shape[1]
    nchunks = (Sk + chunk - 1) // chunk
    Sk_pad = nchunks * chunk
    if Sk_pad != Sk:
        k = jnp.pad(k, ((0, 0), (0, Sk_pad - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sk_pad - Sk), (0, 0), (0, 0)))

    kc = jnp.moveaxis(k.reshape(B, nchunks, chunk, H, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nchunks, chunk, H, hd_v), 1, 0)

    qi = q_offset + jnp.arange(Sq)[:, None]  # (Sq, 1) absolute q positions

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, c_idx = xs
        kj = c_idx * chunk + jnp.arange(chunk)[None, :]  # (1, chunk) absolute
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb, preferred_element_type=jnp.float32)
        s = s * scale
        ok = kj < Sk  # mask padding
        if mode != "full":
            ok = ok & (kj <= qi)
        if mode == "sliding":
            ok = ok & (kj > qi - window)
        s = jnp.where(ok[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(q.dtype), vb)
        acc_new = acc * corr[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, hd_v), jnp.float32)
    # checkpoint the chunk body: without it, scan stashes every chunk's f32
    # scores for backward — re-materializing the S^2 HBM traffic this path
    # exists to avoid (flash backward recomputes p per chunk instead).
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, a0), (kc, vc, jnp.arange(nchunks))
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B, Sq, H, hd)


def _sdpa(q, k, v, mask, policy: ShardingPolicy | None, *, head_sharded: bool,
          cfg: ModelConfig | None = None, mode: str = "full", window: int = 0,
          q_offset=0):
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    use_chunked = (
        cfg is not None
        and not cfg.attn_naive
        and q.shape[1] > 1  # decode stays naive: (B,H,1,Sk) is small
        and k.shape[1] >= cfg.attn_chunk_min_len
    )
    if use_chunked:
        if policy is not None and policy.active:
            hs = policy.model_axis if head_sharded else None
            ss = None if head_sharded else policy.model_axis
            q = constrain(q, policy, policy.data_axes, ss, hs, None)
        return _sdpa_chunked(
            q, k, v, policy, head_sharded=head_sharded, scale=scale,
            mode=mode, window=window, q_offset=q_offset, chunk=cfg.attn_k_chunk,
        )
    return _sdpa_naive(q, k, v, mask, policy, head_sharded=head_sharded, scale=scale)


def _flash_decode(q, ck, cv, k_new, v_new, pos, *, mode: str, window: int,
                  n_rep: int, policy: ShardingPolicy):
    """shard_map flash-decoding over a sequence-sharded KV cache.

    §Perf cycle 5: the einsum decode path makes XLA all-gather the sharded
    cache both for the dynamic position update and for the softmax over the
    sharded length — tens of GiB of collectives per token.  Here each model
    shard updates its local cache slice in place and computes a partial
    (max, denom, weighted-V); the merge is one pmax + two psums of
    (B,H[,hd]) — kilobytes.

    q: (B,1,H,hd); ck/cv: (B,L,KVH,hd) sharded (data: B, model: L);
    k_new/v_new: (B,1,KVH,hd).  Returns (out (B,1,H,hd), ck, cv).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = policy.mesh
    m_ax, da = policy.model_axis, policy.data_axes
    B = q.shape[0]
    dsize = 1
    for a in da:
        dsize *= mesh.shape[a]
    bspec = da if (B % dsize == 0 and B >= dsize) else None
    L = ck.shape[1]
    ring = mode == "sliding" and L == window
    scale = 1.0 / math.sqrt(q.shape[-1])

    def body(q, ck, cv, k_new, v_new, pos):
        m = jax.lax.axis_index(m_ax)
        L_loc = ck.shape[1]
        # --- local in-place cache update -----------------------------------
        slot_g = jnp.mod(pos, L) if ring else pos
        local = slot_g - m * L_loc
        in_range = (local >= 0) & (local < L_loc)
        idx = jnp.clip(local, 0, L_loc - 1)
        cur_k = jax.lax.dynamic_slice(ck, (0, idx, 0, 0), k_new.shape)
        cur_v = jax.lax.dynamic_slice(cv, (0, idx, 0, 0), v_new.shape)
        ck = jax.lax.dynamic_update_slice(
            ck, jnp.where(in_range, k_new.astype(ck.dtype), cur_k), (0, idx, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cv, jnp.where(in_range, v_new.astype(cv.dtype), cur_v), (0, idx, 0, 0)
        )
        # --- local partial attention ---------------------------------------
        kj = m * L_loc + jnp.arange(L_loc)  # global slot ids of my shard
        if ring:
            rpos = _ring_positions(kj, pos, L)
            valid = (pos - rpos >= 0) & (pos - rpos < L) & (rpos >= 0)
        else:
            valid = kj <= pos
        kk = _repeat_kv(ck.astype(q.dtype), n_rep)
        vv = _repeat_kv(cv.astype(q.dtype), n_rep)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[None, None, None, :], s, -1e30)
        mx_loc = jnp.max(s, axis=-1)  # (B,H,1)
        mx = jax.lax.pmax(mx_loc, m_ax)
        pexp = jnp.exp(s - mx[..., None])
        l = jax.lax.psum(jnp.sum(pexp, axis=-1), m_ax)  # (B,H,1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", pexp.astype(q.dtype), vv)
        pv = jax.lax.psum(pv.astype(jnp.float32), m_ax)  # (B,H,1,hd)
        out = (pv / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
        return jnp.moveaxis(out, 1, 2), ck, cv  # (B,1,H,hd)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None, None),  # q replicated over model
            P(bspec, m_ax, None, None),  # cache: L sharded
            P(bspec, m_ax, None, None),
            P(bspec, None, None, None),
            P(bspec, None, None, None),
            P(),
        ),
        out_specs=(
            P(bspec, None, None, None),
            P(bspec, m_ax, None, None),
            P(bspec, m_ax, None, None),
        ),
        check_vma=False,
    )(q, ck, cv, k_new, v_new, pos)


def apply_attention(
    p,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    mode: str,  # "causal" | "sliding" | "full"
    policy: ShardingPolicy | None = None,
    kv_cache: dict | None = None,  # decode: {"k","v"}
    decode_pos: jax.Array | None = None,  # scalar int32 absolute position
    x_cross: jax.Array | None = None,  # cross-attention memory (whisper)
) -> tuple[jax.Array, dict | None]:
    """Self- or cross-attention.  Returns (y, updated_cache)."""
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    n_rep = H // KVH
    B = x.shape[0]

    xkv = x_cross if x_cross is not None else x
    q, k, v = _project_qkv(p, x, xkv, cfg)

    if cfg.pos_embedding == "rope" and x_cross is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    head_sharded = policy.shard_q_heads if policy else False
    if policy is not None and policy.active:
        hs = policy.model_axis if head_sharded else None
        ss = None if head_sharded else policy.model_axis
        q = constrain(q, policy, policy.data_axes, ss, hs, None)

    new_cache = None
    if (
        kv_cache is not None
        and x_cross is None
        and policy is not None
        and policy.active
        and not policy.shard_kv_heads
        and kv_cache["k"].shape[1] % policy.model_size == 0
    ):
        # sequence-sharded cache -> shard_map flash-decoding (§Perf cycle 5)
        out, ck, cv = _flash_decode(
            q, kv_cache["k"], kv_cache["v"], k, v, decode_pos,
            mode=mode, window=cfg.sliding_window, n_rep=n_rep, policy=policy,
        )
        new_cache = {"k": ck, "v": cv}
        out = out.reshape(B, -1, H * hd)
        y = out @ p["wo"].astype(out.dtype)
        return y, new_cache

    if kv_cache is not None and x_cross is None:
        # decode: append this step's k/v at position `decode_pos`
        pos = decode_pos
        ck, cv = kv_cache["k"], kv_cache["v"]  # (B, L, KVH, hd)
        L = ck.shape[1]
        if mode == "sliding" and L == cfg.sliding_window:
            slot = jnp.mod(pos, L)  # ring buffer
        else:
            slot = pos
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
        new_cache = {"k": ck, "v": cv}
        k_full, v_full = ck.astype(x.dtype), cv.astype(x.dtype)
        # mask out unwritten/future slots
        kj = jnp.arange(L)
        if mode == "sliding" and L == cfg.sliding_window:
            # ring buffer: valid iff slot already written (age < window)
            rpos = _ring_positions(kj, pos, L)
            age = pos - rpos
            valid = (age >= 0) & (age < L) & (rpos >= 0)
            mask = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)[None, :]
        else:
            valid = kj <= pos
            mask = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)[None, :]
        out = _sdpa(
            q, _repeat_kv(k_full, n_rep), _repeat_kv(v_full, n_rep),
            mask, policy, head_sharded=head_sharded, cfg=cfg,
        )
    elif kv_cache is not None and x_cross is not None:
        # cross-attention during decode: static memory, no cache update
        mask = jnp.zeros((1, k.shape[1]), jnp.float32)
        out = _sdpa(q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep), mask, policy,
                    head_sharded=head_sharded, cfg=cfg, mode="full")
        new_cache = kv_cache
    else:
        eff_mode = {"causal": "causal", "sliding": "sliding", "full": "full"}[mode]
        use_chunked = (not cfg.attn_naive and q.shape[1] > 1
                       and k.shape[1] >= cfg.attn_chunk_min_len)
        mask = None if use_chunked else _attn_mask(
            q.shape[1], k.shape[1], 0, eff_mode, cfg.sliding_window)
        out = _sdpa(q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep), mask, policy,
                    head_sharded=head_sharded, cfg=cfg, mode=eff_mode,
                    window=cfg.sliding_window, q_offset=0)

    out = out.reshape(B, -1, H * hd)
    y = out @ p["wo"].astype(out.dtype)
    return y, new_cache


def _ring_positions(slots: jax.Array, pos: jax.Array, L) -> jax.Array:
    """Absolute position currently stored in each ring-buffer slot.

    The slot for absolute position t is t % L; slot j currently holds the
    largest t' <= pos with t' % L == j.
    """
    rem = jnp.mod(pos, L)
    base = pos - rem
    cand = base + slots
    return jnp.where(cand <= pos, cand, cand - L)


# ---------------------------------------------------------------------------
# MLA (deepseek multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig):
    D, H = cfg.d_model, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    return {
        "wq_a": _dense_init(ks[0], (D, rq), cfg.param_dtype),
        "q_norm": jnp.ones((rq,), cfg.param_dtype),
        "wq_b": _dense_init(ks[1], (rq, H * (dn + dr)), cfg.param_dtype),
        "wkv_a": _dense_init(ks[2], (D, rkv + dr), cfg.param_dtype),
        "kv_norm": jnp.ones((rkv,), cfg.param_dtype),
        "wk_b": _dense_init(ks[3], (rkv, H * dn), cfg.param_dtype),
        "wv_b": _dense_init(ks[4], (rkv, H * dv), cfg.param_dtype),
        "wo": _dense_init(ks[5], (H * dv, D), cfg.param_dtype),
    }


def _mla_q(p, x, cfg: ModelConfig, positions):
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = _rms_head_norm(p["q_norm"], x @ p["wq_a"].astype(x.dtype))
    q = (cq @ p["wq_b"].astype(x.dtype)).reshape(*x.shape[:-1], H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(p, x, cfg: ModelConfig, positions):
    rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    kv = x @ p["wkv_a"].astype(x.dtype)  # (B,S,rkv+dr)
    c_kv = _rms_head_norm(p["kv_norm"], kv[..., :rkv])
    k_pe = apply_rope(kv[..., None, rkv:], positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_pe  # (B,S,rkv), (B,S,dr)


def apply_mla(
    p,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    mode: str,
    policy: ShardingPolicy | None = None,
    kv_cache: dict | None = None,  # {"ckv": (B,L,rkv), "kpe": (B,L,dr)}
    decode_pos: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    """Multi-head latent attention.  Decode uses the *absorbed* formulation:
    scores from the compressed latent directly, value read-out in latent space
    — the cache holds only (rkv + dr) floats per token."""
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rkv = cfg.kv_lora_rank
    scale = 1.0 / math.sqrt(dn + dr)

    q_nope, q_rope = _mla_q(p, x, cfg, positions)

    if kv_cache is None:
        # train/prefill: expand latents to per-head K/V; fold the shared
        # rope key into a concatenated head dim so the (chunked) SDPA core
        # handles MLA unchanged: q_eff=[q_nope;q_rope], k_eff=[k_nope;k_pe].
        c_kv, k_pe = _mla_kv_latent(p, x, cfg, positions)
        k_nope = (c_kv @ p["wk_b"].astype(x.dtype)).reshape(B, S, H, dn)
        v = (c_kv @ p["wv_b"].astype(x.dtype)).reshape(B, S, H, dv)
        q_eff = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_eff = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, dr))], axis=-1
        )
        # pad v to match the sdpa head dim contract? no: _sdpa allows hd_v != hd_qk
        use_chunked = (not cfg.attn_naive and S > 1 and S >= cfg.attn_chunk_min_len)
        mask = None if use_chunked else _attn_mask(S, S, 0, "causal", 0)
        # _sdpa scales by 1/sqrt(q_eff_dim) == 1/sqrt(dn+dr) = `scale` — correct.
        out = _sdpa(q_eff, k_eff, v, mask, policy,
                    head_sharded=policy.shard_q_heads if policy else False,
                    cfg=cfg, mode="causal", window=0, q_offset=0)
        new_cache = None
    elif (
        policy is not None and policy.active
        and kv_cache["ckv"].shape[1] % policy.model_size == 0
    ):
        # absorbed decode over a sequence-sharded latent cache: shard_map
        # flash merge (§Perf cycle 5), latent read-out psum'ed in rkv space.
        from jax import shard_map
        from jax.sharding import PartitionSpec as PS

        mesh = policy.mesh
        m_ax, da = policy.model_axis, policy.data_axes
        dsize = 1
        for a in da:
            dsize *= mesh.shape[a]
        bspec = da if (B % dsize == 0 and B >= dsize) else None
        c_new, kpe_new = _mla_kv_latent(p, x, cfg, positions)
        wk_b = p["wk_b"].astype(x.dtype).reshape(rkv, H, dn)
        wv_b = p["wv_b"].astype(x.dtype).reshape(rkv, H, dv)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, wk_b)
        L = kv_cache["ckv"].shape[1]

        def body(q_lat, q_rope, ckv, kpe, c_new, kpe_new, pos):
            m = jax.lax.axis_index(m_ax)
            L_loc = ckv.shape[1]
            local = pos - m * L_loc
            in_range = (local >= 0) & (local < L_loc)
            idx = jnp.clip(local, 0, L_loc - 1)
            cur_c = jax.lax.dynamic_slice(ckv, (0, idx, 0), c_new.shape)
            cur_p = jax.lax.dynamic_slice(kpe, (0, idx, 0), kpe_new.shape)
            ckv = jax.lax.dynamic_update_slice(
                ckv, jnp.where(in_range, c_new.astype(ckv.dtype), cur_c), (0, idx, 0))
            kpe = jax.lax.dynamic_update_slice(
                kpe, jnp.where(in_range, kpe_new.astype(kpe.dtype), cur_p), (0, idx, 0))
            kj = m * L_loc + jnp.arange(L_loc)
            valid = kj <= pos
            ckv_c = ckv.astype(q_lat.dtype)
            s = (
                jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv_c,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bqhd,bkd->bhqk", q_rope, kpe.astype(q_lat.dtype),
                             preferred_element_type=jnp.float32)
            ) * scale
            s = jnp.where(valid[None, None, None, :], s, -1e30)
            mx = jax.lax.pmax(jnp.max(s, axis=-1), m_ax)
            pexp = jnp.exp(s - mx[..., None])
            l = jax.lax.psum(jnp.sum(pexp, axis=-1), m_ax)
            o_lat = jnp.einsum("bhqk,bkr->bhqr", pexp.astype(q_lat.dtype), ckv_c)
            o_lat = jax.lax.psum(o_lat.astype(jnp.float32), m_ax)
            o_lat = (o_lat / jnp.maximum(l[..., None], 1e-30)).astype(q_lat.dtype)
            return jnp.moveaxis(o_lat, 1, 2), ckv, kpe  # (B,1,H,rkv)

        o_lat, ckv, kpe = shard_map(
            body, mesh=mesh,
            in_specs=(
                PS(bspec, None, None, None), PS(bspec, None, None, None),
                PS(bspec, m_ax, None), PS(bspec, m_ax, None),
                PS(bspec, None, None), PS(bspec, None, None), PS(),
            ),
            out_specs=(
                PS(bspec, None, None, None),
                PS(bspec, m_ax, None), PS(bspec, m_ax, None),
            ),
            check_vma=False,
        )(q_lat, q_rope, kv_cache["ckv"], kv_cache["kpe"], c_new, kpe_new,
          decode_pos)
        new_cache = {"ckv": ckv, "kpe": kpe}
        out = jnp.einsum("bqhr,rhd->bqhd", o_lat, wv_b)
    else:
        # absorbed decode
        pos = decode_pos
        c_new, kpe_new = _mla_kv_latent(p, x, cfg, positions)
        ckv = jax.lax.dynamic_update_slice(
            kv_cache["ckv"], c_new.astype(kv_cache["ckv"].dtype), (0, pos, 0)
        )
        kpe = jax.lax.dynamic_update_slice(
            kv_cache["kpe"], kpe_new.astype(kv_cache["kpe"].dtype), (0, pos, 0)
        )
        new_cache = {"ckv": ckv, "kpe": kpe}
        L = ckv.shape[1]
        wk_b = p["wk_b"].astype(x.dtype).reshape(rkv, H, dn)
        wv_b = p["wv_b"].astype(x.dtype).reshape(rkv, H, dv)
        # absorb: q_lat = q_nope @ W_UK  -> (B,S,H,rkv)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, wk_b)
        ckv_c = ckv.astype(x.dtype)
        scores = (
            jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv_c, preferred_element_type=jnp.float32)
            + jnp.einsum("bqhd,bkd->bhqk", q_rope, kpe.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        ) * scale
        valid = jnp.arange(L) <= pos
        scores = scores + jnp.where(valid, 0.0, -1e30)[None, None, None, :]
        if policy is not None and policy.active:
            scores = constrain(scores, policy, policy.data_axes, policy.model_axis, None, None)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhqk,bkr->bqhr", probs, ckv_c)  # latent read-out
        out = jnp.einsum("bqhr,rhd->bqhd", o_lat, wv_b)

    out = out.reshape(B, S, H * dv)
    y = out @ p["wo"].astype(out.dtype)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_gated:
        return {
            "w_gate": _dense_init(ks[0], (D, F), cfg.param_dtype),
            "w_up": _dense_init(ks[1], (D, F), cfg.param_dtype),
            "w_down": _dense_init(ks[2], (F, D), cfg.param_dtype),
        }
    return {
        "w_up": _dense_init(ks[0], (D, F), cfg.param_dtype),
        "w_down": _dense_init(ks[1], (F, D), cfg.param_dtype),
        "b_up": jnp.zeros((F,), cfg.param_dtype),
        "b_down": jnp.zeros((D,), cfg.param_dtype),
    }


def apply_mlp(p, x: jax.Array, cfg: ModelConfig,
              policy: ShardingPolicy | None = None) -> jax.Array:
    if "w_gate" in p:
        h = jax.nn.silu(x @ p["w_gate"].astype(x.dtype)) * (x @ p["w_up"].astype(x.dtype))
    else:
        h = jax.nn.gelu(x @ p["w_up"].astype(x.dtype) + p["b_up"].astype(x.dtype))
    if policy is not None and policy.active:
        h = constrain(h, policy, policy.data_axes, None, policy.model_axis)
    y = h @ p["w_down"].astype(x.dtype)
    if "b_down" in p:
        y = y + p["b_down"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# MoE: shared experts + routed top-k with expert parallelism
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig):
    D, E, F = cfg.d_model, cfg.padded_n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (D, E), cfg.param_dtype, scale=0.02),
        "we_gate": _dense_init(ks[1], (E, D, F), cfg.param_dtype),
        "we_up": _dense_init(ks[2], (E, D, F), cfg.param_dtype),
        "we_down": _dense_init(ks[3], (E, F, D), cfg.param_dtype),
    }
    if cfg.n_shared_experts > 0:
        sf = cfg.shared_d_ff or cfg.moe_d_ff * cfg.n_shared_experts
        p["shared"] = init_mlp(ks[4], cfg, d_ff=sf)
    return p


def _router_probs(p, x_flat: jax.Array, cfg: ModelConfig):
    """Router in f32.  Padded (dead) experts get -inf logits."""
    E, E_real = cfg.padded_n_experts, cfg.n_experts
    # f32 accumulation without materializing an f32 copy of (T, D)
    logits = jnp.einsum(
        "td,de->te", x_flat, p["router"].astype(x_flat.dtype),
        preferred_element_type=jnp.float32,
    )
    if E != E_real:
        pad_mask = jnp.arange(E) >= E_real
        logits = jnp.where(pad_mask, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)  # (T,k)
    gate_vals = gate_vals / jnp.clip(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    return probs, gate_vals, expert_idx


def moe_aux_loss(probs: jax.Array, expert_idx: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Switch-style load-balance loss: E * Σ_e f_e · P_e."""
    E = cfg.padded_n_experts
    T = probs.shape[0]
    counts = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0)
    f = counts / (T * cfg.top_k)
    pmean = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * pmean)


def apply_moe_dense(p, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """Reference MoE: every expert computed densely for every token, combined
    with top-k gates.  O(T·E·D·F) — only for small/smoke configs and as the
    correctness oracle for the expert-parallel path."""
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    probs, gates, idx = _router_probs(p, xf, cfg)
    # (T, E, F) all-expert forward
    h = jnp.einsum("td,edf->tef", xf, p["we_gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", xf, p["we_up"].astype(x.dtype))
    eo = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, p["we_down"].astype(x.dtype))
    onehot = jax.nn.one_hot(idx, cfg.padded_n_experts, dtype=x.dtype)  # (T,k,E)
    comb = jnp.einsum("tk,tke->te", gates.astype(x.dtype), onehot)
    y = jnp.einsum("te,ted->td", comb, eo)
    aux = moe_aux_loss(probs, idx, cfg)
    y = y.reshape(B, S, D)
    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, cfg)
    return y, aux


def apply_moe_ep(
    p, x: jax.Array, cfg: ModelConfig, policy: ShardingPolicy
) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE under ``shard_map`` over the model axis.

    Baseline formulation (DESIGN.md §5): tokens replicated over ``model``;
    each shard owns E/model_size experts, dispatches only assignments routed
    to its local experts into a capacity-padded ``(E_loc, C, D)`` buffer, runs
    the batched expert matmuls, and contributes its partial combine via one
    ``psum``.  No all-to-all; communication is a single (T, D) reduce.
    The §Perf hillclimb replaces this with an all-to-all dispatch for the
    train shapes.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = policy.mesh
    msize = policy.model_size
    E = cfg.padded_n_experts
    assert E % msize == 0, (E, msize)
    E_loc = E // msize
    B, S, D = x.shape
    T = B * S
    # static capacity per expert (per data shard)
    data_size = 1
    for a in policy.data_axes:
        data_size *= mesh.shape[a]
    T_loc = max(T // data_size, 1)
    C = max(int(math.ceil(T_loc * cfg.top_k / E * cfg.capacity_factor)), cfg.top_k)

    fsdp = policy.fsdp_params
    da = policy.data_axes
    dsize = 1
    for a in da:
        dsize *= mesh.shape[a]

    # --- decode variant (§Perf cycle 7): weights-stationary 2D EP ----------
    # One token per sequence: gathering all B·1 tokens costs ~MBs while
    # gathering FSDP expert weights costs ~GBs per layer.  Shard experts over
    # model × data (E/256 per chip, never moved), replicate the tiny token
    # set, psum contributions over the whole mesh.
    if (S == 1 and policy.serving and fsdp
            and E % (msize * dsize) == 0 and B % dsize == 0):
        E_loc2 = E // (msize * dsize)

        def body_decode(router, wg, wu, wd, xb):
            m = jax.lax.axis_index(policy.model_axis)
            d = jax.lax.axis_index(da)
            xg = jax.lax.all_gather(xb, da, axis=0, tiled=True)  # (B,1,D)
            xf = xg.reshape(-1, D)
            probs, gates, idx = _router_probs({"router": router}, xf, cfg)
            aux = moe_aux_loss(probs, idx, cfg)
            e0 = (m * dsize + d) * E_loc2  # my expert block start
            # per-token gate for each of my local experts: (T, E_loc2)
            local_ids = e0 + jnp.arange(E_loc2)
            sel = (idx[:, :, None] == local_ids[None, None, :])
            gate_e = jnp.sum(jnp.where(sel, gates[:, :, None], 0.0), axis=1)
            h = jnp.einsum("td,edf->tef", xf, wg.astype(xf.dtype))
            u = jnp.einsum("td,edf->tef", xf, wu.astype(xf.dtype))
            yc = jnp.einsum(
                "tef,efd->td",
                jax.nn.silu(h) * u * gate_e.astype(h.dtype)[:, :, None],
                wd.astype(xf.dtype),
            )
            y = jax.lax.psum(yc, (policy.model_axis, *da))  # (T, D) full batch
            B_loc = xb.shape[0]
            y = jax.lax.dynamic_slice(y, (d * B_loc, 0), (B_loc, D))
            return y.reshape(xb.shape), jax.lax.pmean(aux, policy.model_axis)

        e_spec = P((policy.model_axis, *da))
        y, aux = shard_map(
            body_decode,
            mesh=mesh,
            in_specs=(P(), e_spec, e_spec, e_spec, P(da, None, None)),
            out_specs=(P(da, None, None), P()),
            check_vma=False,
        )(p["router"], p["we_gate"], p["we_up"], p["we_down"], x)
        if "shared" in p:
            y = y + apply_mlp(p["shared"], x, cfg, policy)
        return y, aux

    def body(router, we_gate, we_up, we_down, xb):
        # xb: (B_loc, S, D) — replicated over model, sharded over data.
        # Expert weights arrive FSDP-sharded (E_loc, D/|data|, F) and are
        # gathered just-in-time (ZeRO-3 style): persistent storage stays
        # fully sharded, only one layer's experts are ever materialized.
        if fsdp:
            we_gate = jax.lax.all_gather(we_gate, da, axis=1, tiled=True)
            we_up = jax.lax.all_gather(we_up, da, axis=1, tiled=True)
            we_down = jax.lax.all_gather(we_down, da, axis=2, tiled=True)
        m = jax.lax.axis_index(policy.model_axis)
        xf = xb.reshape(-1, D)
        t_loc = xf.shape[0]
        probs, gates, idx = _router_probs({"router": router}, xf, cfg)
        aux = moe_aux_loss(probs, idx, cfg)

        flat_e = idx.reshape(-1)  # (T*k,)
        flat_g = gates.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t_loc), cfg.top_k)
        local_e = flat_e - m * E_loc
        is_local = (local_e >= 0) & (local_e < E_loc)

        # rank of each assignment within its (local) expert, via sort
        sort_key = jnp.where(is_local, local_e, E_loc)  # non-local last
        order = jnp.argsort(sort_key, stable=True)
        sorted_e = sort_key[order]
        # position within expert = index - start offset of that expert
        counts = jnp.bincount(sorted_e, length=E_loc + 1)
        starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)])[:-1]
        ranks_sorted = jnp.arange(sorted_e.shape[0]) - starts[jnp.clip(sorted_e, 0, E_loc)]
        ranks = jnp.zeros_like(ranks_sorted).at[order].set(ranks_sorted)

        keep = is_local & (ranks < C)
        slot = jnp.where(keep, local_e * C + ranks, E_loc * C)  # overflow slot

        # Work in SLOT space (E_loc*C ≈ T·k·cf/model_size entries), never in
        # assignment space (T·k entries): the (T·k, D) gathers would dominate
        # the step's memory (14 GiB/layer for deepseek-v3 train_4k).
        n_slots = E_loc * C
        tok_per_slot = jnp.full((n_slots + 1,), t_loc, jnp.int32).at[slot].set(
            flat_t.astype(jnp.int32)
        )[:n_slots]
        gate_per_slot = jnp.zeros((n_slots + 1,), jnp.float32).at[slot].set(
            jnp.where(keep, flat_g, 0.0)
        )[:n_slots]
        valid_slot = jnp.zeros((n_slots + 1,), bool).at[slot].set(keep)[:n_slots]

        xf_pad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], axis=0)
        buf = xf_pad[tok_per_slot] * valid_slot[:, None].astype(xf.dtype)
        buf = buf.reshape(E_loc, C, D)

        h = jnp.einsum("ecd,edf->ecf", buf, we_gate.astype(xb.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf, we_up.astype(xb.dtype))
        eo = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, we_down.astype(xb.dtype))

        contrib = eo.reshape(n_slots, D) * gate_per_slot[:, None].astype(eo.dtype)
        y_part = jnp.zeros((t_loc + 1, D), xb.dtype).at[tok_per_slot].add(contrib)[:t_loc]
        y = jax.lax.psum(y_part, policy.model_axis)
        aux = jax.lax.pmean(aux, policy.model_axis)
        return y.reshape(xb.shape), aux

    m_ax = policy.model_axis
    if fsdp:
        spec_gu = P(m_ax, da, None)  # matches param_specs FSDP layout
        spec_d = P(m_ax, None, da)
    else:
        spec_gu = spec_d = P(m_ax)
    y, aux = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), spec_gu, spec_gu, spec_d, P(da, None, None)),
        out_specs=(P(da, None, None), P()),
        check_vma=False,
    )(p["router"], p["we_gate"], p["we_up"], p["we_down"], x)

    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, cfg, policy)
    return y, aux


def apply_moe(p, x, cfg: ModelConfig, policy: ShardingPolicy | None):
    if policy is not None and policy.active:
        return apply_moe_ep(p, x, cfg, policy)
    return apply_moe_dense(p, x, cfg)


# ---------------------------------------------------------------------------
# Mamba2 (SSD — state space duality)
# ---------------------------------------------------------------------------


def init_mamba(key, cfg: ModelConfig):
    D = cfg.d_model
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    proj_out = 2 * di + 2 * N + H  # z, x, B, C, dt
    ks = jax.random.split(key, 4)
    return {
        "in_proj": _dense_init(ks[0], (D, proj_out), cfg.param_dtype),
        "conv_w": _dense_init(ks[1], (cfg.conv_width, di + 2 * N), cfg.param_dtype, scale=0.2),
        "conv_b": jnp.zeros((di + 2 * N,), cfg.param_dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(cfg.param_dtype),
        "D_skip": jnp.ones((H,), cfg.param_dtype),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((H,), 0.01))).astype(cfg.param_dtype),
        "norm": jnp.ones((di,), cfg.param_dtype),
        "out_proj": _dense_init(ks[2], (di, D), cfg.param_dtype),
    }


def _causal_conv(xBC: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv along S. xBC: (B,S,C); w: (W,C)."""
    W = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC, dtype=jnp.float32)
    for i in range(W):
        out = out + pad[:, i : i + xBC.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return jax.nn.silu(out + b.astype(jnp.float32)).astype(xBC.dtype)


def _ssd_chunked(xh, dt, A, Bm, Cm, chunk: int):
    """Chunked SSD scan.

    xh: (B,S,H,P) inputs; dt: (B,S,H) (positive); A: (H,) (negative);
    Bm, Cm: (B,S,N) (single group).  Returns y: (B,S,H,P).
    """
    Bsz, S, H, Pd = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    if S % Q:  # pad tail with zeros (dt=0 -> unit decay, B=0 -> no state writes)
        pad = Q - S % Q
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S_pad = S + pad
    else:
        S_pad = S
    nc = S_pad // Q

    xc = xh.reshape(Bsz, nc, Q, H, Pd)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)
    del xh, dt, Bm, Cm

    a = dtc * A  # (B,nc,Q,H) log-decay per step (negative)
    cum_a = jnp.cumsum(a, axis=2)  # inclusive cumsum within chunk

    # ---- intra-chunk (diagonal block) ----
    # L[t,s] = exp(cum_a[t] - cum_a[s]) for t >= s (decay from s+1..t)
    rel = cum_a[:, :, :, None, :] - cum_a[:, :, None, :, :]  # (B,nc,Q,Q,H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    Lmat = jnp.where(tri[None, None, :, :, None], jnp.exp(rel), 0.0)
    scores = jnp.einsum("bctn,bcsn->bcts", Cc.astype(jnp.float32), Bc.astype(jnp.float32))
    M = scores[..., None] * Lmat  # (B,nc,Q,Q,H)
    xdt = xc.astype(jnp.float32) * dtc[..., None].astype(jnp.float32)
    y_diag = jnp.einsum("bctsh,bcshp->bcthp", M, xdt)

    # ---- chunk states ----
    # state_c = Σ_s exp(cum_a[Q-1] - cum_a[s]) dt_s B_s ⊗ x_s  : (B,nc,H,P,N)
    decay_to_end = jnp.exp(cum_a[:, :, -1:, :] - cum_a)  # (B,nc,Q,H)
    st = jnp.einsum(
        "bcsh,bcsn,bcshp->bchpn",
        (decay_to_end * dtc).astype(jnp.float32),
        Bc.astype(jnp.float32),
        xc.astype(jnp.float32),
    )

    # ---- inter-chunk recurrence (sequential over nc chunks) ----
    chunk_decay = jnp.exp(cum_a[:, :, -1, :])  # (B,nc,H)

    def step(carry, inp):
        st_c, dec_c = inp  # (B,H,P,N), (B,H)
        new = carry * dec_c[:, :, None, None] + st_c
        return new, carry  # emit state *entering* this chunk

    init = jnp.zeros((Bsz, H, Pd, N), jnp.float32)
    _, prev_states = jax.lax.scan(
        step, init,
        (jnp.moveaxis(st, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (B,nc,H,P,N)

    # ---- inter-chunk contribution ----
    y_off = jnp.einsum(
        "bctn,bcth,bchpn->bcthp",
        Cc.astype(jnp.float32), jnp.exp(cum_a), prev_states,
    )

    y = (y_diag + y_off).reshape(Bsz, S_pad, H, Pd)
    return y[:, :S]


def apply_mamba(
    p,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    policy: ShardingPolicy | None = None,
    cache: dict | None = None,  # {"conv": (B,W-1,di+2N), "ssm": (B,H,P,N)}
    decode_pos: jax.Array | None = None,
) -> tuple[jax.Array, dict | None]:
    """Mamba2 mixer.  Train/prefill: chunked SSD.  Decode: O(1) recurrence."""
    B, S, D = x.shape
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    Pd = cfg.ssm_head_dim

    proj = x @ p["in_proj"].astype(x.dtype)  # (B,S,2di+2N+H)
    z, xi, Bm, Cm, dt_raw = jnp.split(proj, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
    xBC = jnp.concatenate([xi, Bm, Cm], axis=-1)

    new_cache = None
    if cache is None:
        xBC = _causal_conv(xBC, p["conv_w"], p["conv_b"])
    else:
        # decode: use conv window cache (holds previous W-1 inputs)
        W = cfg.conv_width
        window = jnp.concatenate([cache["conv"].astype(xBC.dtype), xBC], axis=1)  # (B,W,ch)
        acc = jnp.zeros_like(xBC, dtype=jnp.float32)
        for i in range(W):
            acc = acc + window[:, i : i + 1, :].astype(jnp.float32) * p["conv_w"][i].astype(jnp.float32)
        xBC = jax.nn.silu(acc + p["conv_b"].astype(jnp.float32)).astype(xBC.dtype)
        new_conv = window[:, 1:, :]

    xi, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    xh = xi.reshape(B, S, H, Pd)
    if policy is not None and policy.active and policy.shard_ssm_heads:
        xh = constrain(xh, policy, policy.data_axes, None, policy.model_axis, None)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,) negative
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    if cache is None:
        y = _ssd_chunked(xh, dt, A, Bm, Cm, cfg.ssm_chunk)
    else:
        # one-step recurrence
        st = cache["ssm"].astype(jnp.float32)  # (B,H,P,N)
        a1 = jnp.exp(dt[:, 0, :] * A)  # (B,H)
        dBx = jnp.einsum(
            "bh,bn,bhp->bhpn", dt[:, 0, :], Bm[:, 0, :].astype(jnp.float32),
            xh[:, 0].astype(jnp.float32),
        )
        st = st * a1[:, :, None, None] + dBx
        y = jnp.einsum("bhpn,bn->bhp", st, Cm[:, 0, :].astype(jnp.float32))[:, None]
        new_cache = {"conv": new_conv.astype(cache["conv"].dtype),
                     "ssm": st.astype(cache["ssm"].dtype)}

    y = y + p["D_skip"].astype(jnp.float32)[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, S, di)
    # gated RMS norm (mamba2): norm(y * silu(z))
    y = y * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(ms + 1e-6) * p["norm"].astype(jnp.float32)
    out = y.astype(x.dtype) @ p["out_proj"].astype(x.dtype)
    return out, new_cache
