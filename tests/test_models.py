"""Model-stack tests: per-arch smoke (reduced configs), decode/prefill
consistency, MoE expert-parallel vs dense oracle, SSD chunked vs sequential,
chunked vs naive attention."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHITECTURES, get_reduced
from repro.models import kvcache, layers, transformer
from repro.models.config import ModelConfig
from repro.models.sharding import make_policy


def _batch_for(cfg, B=2, S=16, seed=1):
    tokens = jax.random.randint(jax.random.key(seed), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.frontend == "vision_stub":
        batch["prefix_embeds"] = jax.random.normal(
            jax.random.key(seed + 1), (B, cfg.num_prefix_tokens, cfg.frontend_dim),
            jnp.float32,
        )
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            jax.random.key(seed + 1), (B, cfg.encoder_seq_len, cfg.frontend_dim),
            jnp.float32,
        )
    return batch


# ---------------------------------------------------------------------------
# per-arch smoke: REDUCED variant, one forward + one train step on CPU
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHITECTURES)
def test_arch_smoke_forward_and_train_step(arch):
    from repro.launch.steps import make_train_step
    from repro.optim import sgd

    cfg = get_reduced(arch)
    params = transformer.init_params(jax.random.key(0), cfg)
    batch = _batch_for(cfg)

    # forward: logits shape + finite
    logits, _, aux = transformer.forward(
        params, batch["tokens"], cfg,
        prefix_embeds=batch.get("prefix_embeds"), frames=batch.get("frames"),
    )
    B, S = batch["tokens"].shape
    assert logits.shape == (B, S, cfg.padded_vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    # one train step: loss finite, params change, no NaNs anywhere
    opt = sgd(0.1)
    step = jax.jit(make_train_step(cfg, opt, None))
    new_params, _, loss = step(params, opt.init(params), batch)
    assert bool(jnp.isfinite(loss)), arch
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        params, new_params,
    )
    assert max(jax.tree_util.tree_leaves(moved)) > 0, "params did not move"
    for leaf in jax.tree_util.tree_leaves(new_params):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32)))), arch


@pytest.mark.slow
@pytest.mark.parametrize(
    "arch",
    ["gemma3-4b", "mamba2-780m", "zamba2-1.2b", "deepseek-v3-671b",
     "qwen3-14b", "whisper-large-v3", "qwen2-moe-a2.7b"],
)
def test_decode_matches_prefill(arch):
    cfg = dataclasses.replace(get_reduced(arch), dtype=jnp.float32)
    params = transformer.init_params(jax.random.key(0), cfg)
    B, S = 2, 12
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    memory = None
    kw = {}
    if cfg.is_encoder_decoder:
        frames = jax.random.normal(
            jax.random.key(2), (B, cfg.encoder_seq_len, cfg.frontend_dim), jnp.float32
        )
        memory = transformer.encode(params, frames, cfg)
        kw["memory"] = memory
    logits_pre, _, _ = transformer.forward(params, tokens, cfg, **kw)
    cache = kvcache.init_cache(cfg, B, 16, dtype=jnp.float32)
    outs = []
    for t in range(S):
        lg, cache = transformer.decode_step(
            params, tokens[:, t : t + 1], cache, jnp.asarray(t, jnp.int32), cfg,
            memory=memory,
        )
        outs.append(lg)
    err = float(jnp.max(jnp.abs(logits_pre - jnp.concatenate(outs, axis=1))))
    assert err < 2e-3, (arch, err)


# ---------------------------------------------------------------------------
# layer-level equivalences
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["causal", "sliding", "full"])
def test_chunked_attention_matches_naive(mode):
    cfg = ModelConfig(
        name="t", arch_type="dense", n_layers=1, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab_size=100, sliding_window=48,
        attn_chunk_min_len=1, attn_k_chunk=37,
    )
    p = layers.init_attention(jax.random.key(0), cfg)
    B, S = 2, 100
    x = jax.random.normal(jax.random.key(1), (B, S, 64), jnp.float32)
    pos = jnp.arange(S)[None, :].repeat(B, 0)
    y_chunk, _ = layers.apply_attention(p, x, cfg, positions=pos, mode=mode)
    y_naive, _ = layers.apply_attention(
        p, x, dataclasses.replace(cfg, attn_naive=True), positions=pos, mode=mode
    )
    np.testing.assert_allclose(y_chunk, y_naive, atol=3e-5)


def test_mla_chunked_matches_naive():
    cfg = ModelConfig(
        name="t", arch_type="dense", n_layers=1, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=128, vocab_size=100, attn_impl="mla", q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        attn_chunk_min_len=1, attn_k_chunk=33,
    )
    p = layers.init_mla(jax.random.key(2), cfg)
    x = jax.random.normal(jax.random.key(3), (2, 100, 64), jnp.float32)
    pos = jnp.arange(100)[None, :].repeat(2, 0)
    y_c, _ = layers.apply_mla(p, x, cfg, positions=pos, mode="causal")
    y_n, _ = layers.apply_mla(
        p, x, dataclasses.replace(cfg, attn_naive=True), positions=pos, mode="causal"
    )
    np.testing.assert_allclose(y_c, y_n, atol=3e-5)


def test_moe_ep_matches_dense_oracle():
    cfg = ModelConfig(
        name="t", arch_type="moe", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
        d_ff=64, vocab_size=100, n_experts=4, top_k=2, moe_d_ff=48,
        n_shared_experts=1, shared_d_ff=48, capacity_factor=4.0,
    )
    p = layers.init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 8, 32), jnp.float32)
    y_dense, aux_d = layers.apply_moe_dense(p, x, cfg)
    from repro.launch.mesh import make_auto_mesh

    mesh = make_auto_mesh((1, 1), ("data", "model"))
    pol = make_policy(cfg, mesh)
    y_ep, aux_e = jax.jit(lambda p_, x_: layers.apply_moe_ep(p_, x_, cfg, pol))(p, x)
    np.testing.assert_allclose(y_dense, y_ep, atol=1e-4)
    np.testing.assert_allclose(aux_d, aux_e, rtol=1e-5)


def test_moe_padded_experts_never_routed():
    cfg = ModelConfig(
        name="t", arch_type="moe", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2,
        d_ff=32, vocab_size=50, n_experts=3, expert_pad_to=4, top_k=2, moe_d_ff=24,
    )
    assert cfg.padded_n_experts == 4
    p = layers.init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (1, 32, 16), jnp.float32)
    probs, gates, idx = layers._router_probs(p, x.reshape(-1, 16), cfg)
    assert int(jnp.max(idx)) < 3  # pad expert (id 3) never selected


@pytest.mark.slow
def test_ssd_chunked_matches_sequential():
    cfg = ModelConfig(
        name="t", arch_type="ssm", n_layers=1, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=0, vocab_size=100, ssm_state=16, ssm_head_dim=16, ssm_chunk=8,
    )
    p = layers.init_mamba(jax.random.key(0), cfg)
    B, S = 2, 37  # deliberately not a multiple of the chunk
    x = jax.random.normal(jax.random.key(1), (B, S, 64), jnp.float32)
    y_full, _ = layers.apply_mamba(p, x, cfg)
    di, N, H, Pd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    cache = {
        "conv": jnp.zeros((B, cfg.conv_width - 1, di + 2 * N)),
        "ssm": jnp.zeros((B, H, Pd, N)),
    }
    ys = []
    for t in range(S):
        yt, cache = layers.apply_mamba(
            p, x[:, t : t + 1], cfg, cache=cache, decode_pos=jnp.asarray(t)
        )
        ys.append(yt)
    np.testing.assert_allclose(y_full, jnp.concatenate(ys, 1), atol=1e-3)


def test_segments_cover_all_layers():
    from repro.models.config import plan_segments

    for arch in ARCHITECTURES:
        cfg = get_reduced(arch)
        segs = plan_segments(cfg)
        assert sum(s.n_layers for s in segs) == cfg.n_layers, arch
        full = get_reduced(arch)  # full config pattern check
        from repro.configs import get_config

        cfg_full = get_config(arch)
        segs_full = plan_segments(cfg_full)
        assert sum(s.n_layers for s in segs_full) == cfg_full.n_layers, arch


def test_param_count_estimate_close():
    """Closed-form estimate used for MODEL_FLOPS must track actual params."""
    import numpy as np

    for arch in ARCHITECTURES:
        cfg = get_reduced(arch)
        params = transformer.init_params(jax.random.key(0), cfg)
        actual = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
        est = cfg.param_count_estimate()
        assert abs(est - actual) / actual < 0.35, (arch, est, actual)
