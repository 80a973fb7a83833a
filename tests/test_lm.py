"""LM stack: attention equivalences, cache semantics, MoE dispatch, RoPE."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.lm import model as LM
from repro.models.lm.config import (AttnConfig, LayerConfig, LMConfig,
                                    MoEConfig, Segment)

KEY = jax.random.PRNGKey(3)


def _dense_reference_attention(q, k, v, causal, window, softcap, scale):
    """O(S^2) reference."""
    b, sq, h, d = q.shape
    _, skv, hkv, dv = v.shape
    g = h // hkv
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k).astype(jnp.float32)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= qp - kp < window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@pytest.mark.parametrize("window", [None, 7])
@pytest.mark.parametrize("softcap", [None, 30.0])
@pytest.mark.parametrize("block", [4, 16, 64])
def test_blockwise_attention_matches_dense(window, softcap, block):
    b, s, h, hkv, d = 2, 33, 4, 2, 8
    q = jax.random.normal(KEY, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d))
    out = LM.blockwise_attention(q, k, v, causal=True, window=window,
                                 softcap=softcap, q_offset=0, kv_len=s,
                                 block=block, scale=d**-0.5)
    ref = _dense_reference_attention(q, k, v, True, window, softcap, d**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_decode_matches_blockwise_last_row():
    b, s, h, hkv, d = 2, 16, 4, 2, 8
    q = jax.random.normal(KEY, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d))
    full = LM.blockwise_attention(q, k, v, causal=True, window=None,
                                  softcap=None, q_offset=0, kv_len=s,
                                  scale=1.0)
    dec = LM.decode_attention(q[:, -1:], k, v, softcap=None, kv_len=s,
                              scale=1.0)
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-5)


def test_rope_rotation_property():
    """RoPE: dot(q_i, k_j) depends only on i-j."""
    d = 16
    q = jax.random.normal(KEY, (1, 1, 1, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 1, 1, d))
    def dot_at(i, j):
        qi = LM.rope(q, jnp.asarray([i]), 10000.0)
        kj = LM.rope(k, jnp.asarray([j]), 10000.0)
        return float(jnp.sum(qi * kj))
    np.testing.assert_allclose(dot_at(3, 1), dot_at(10, 8), rtol=1e-5)
    np.testing.assert_allclose(dot_at(5, 5), dot_at(0, 0), rtol=1e-5)
    assert abs(dot_at(5, 1) - dot_at(5, 2)) > 1e-6


def _tiny(moe_cf=None, window=None):
    gqa = AttnConfig(kind="gqa", n_heads=4, n_kv_heads=2, d_head=16,
                     window=window)
    moe = None if moe_cf is None else MoEConfig(
        n_experts=8, top_k=2, d_ff=32, n_shared=1, d_ff_shared=32,
        capacity_factor=moe_cf)
    layer = LayerConfig(gqa, d_ff=64) if moe is None else \
        LayerConfig(gqa, moe=moe)
    return LMConfig(name="t", d_model=32, vocab=101,
                    segments=(Segment(2, (layer,)),))


def test_moe_no_drop_matches_decode():
    """With capacity >= T, decode == full-forward last token (no drops)."""
    cfg = _tiny(moe_cf=16.0)
    params = LM.init_params(KEY, cfg, dtype=jnp.float32)
    tokens = jax.random.randint(KEY, (2, 12), 0, cfg.vocab)
    logits, _, _ = LM.forward(params, tokens, cfg)
    caches = LM.init_cache(cfg, 2, 16, dtype=jnp.float32)
    _, _, caches = LM.forward(params, tokens[:, :-1], cfg, caches=caches,
                              cache_pos=0, kv_len=11)
    dec = jax.jit(LM.make_decode_step(cfg))
    lg, _ = dec(params, caches, tokens[:, -1:], jnp.asarray(11, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(logits[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_moe_capacity_drops_tokens_gracefully():
    cfg = _tiny(moe_cf=0.1)      # aggressive drops
    params = LM.init_params(KEY, cfg, dtype=jnp.float32)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab)
    logits, aux, _ = LM.forward(params, tokens, cfg)
    assert not np.isnan(np.asarray(logits)).any()
    assert np.isfinite(float(aux))


def test_moe_flops_scale_with_topk_not_experts():
    """Compiled FLOPs must track active experts (capacity dispatch), not a
    dense all-experts compute."""
    x = jax.random.normal(KEY, (64, 32))
    m8 = MoEConfig(n_experts=8, top_k=2, d_ff=16)
    m32 = MoEConfig(n_experts=32, top_k=2, d_ff=16)
    def flops(m):
        p = LM.ffn_params(jax.random.fold_in(KEY, m.n_experts),
                          _tiny(), LayerConfig(AttnConfig(), moe=m), jnp.float32)
        c = jax.jit(lambda xx: LM.moe_ffn(p, xx, m)[0]).lower(x).compile()
        return c.cost_analysis().get("flops", 0.0)
    f8, f32 = flops(m8), flops(m32)
    # 4x experts at fixed top-k: expert GEMM flops stay ~constant (capacity
    # shrinks as 1/E); total must grow far less than 4x
    assert f32 < 2.0 * f8, (f8, f32)


def test_window_ring_cache_decode_long():
    """Decode far past the window: ring cache must equal full-cache result."""
    cfg_ring = _tiny(window=8)
    params = LM.init_params(KEY, cfg_ring, dtype=jnp.float32)
    s = 24
    tokens = jax.random.randint(KEY, (1, s), 0, cfg_ring.vocab)
    # reference: full forward over s+1 tokens
    nxt = jax.random.randint(jax.random.fold_in(KEY, 9), (1, 1),
                             0, cfg_ring.vocab)
    full, _, _ = LM.forward(params, jnp.concatenate([tokens, nxt], 1),
                            cfg_ring)
    caches = LM.init_cache(cfg_ring, 1, s + 8, dtype=jnp.float32)
    assert jax.tree.leaves(caches)[0].shape[2] == 8     # ring-buffered
    _, _, caches = LM.forward(params, tokens, cfg_ring, caches=caches,
                              cache_pos=0, kv_len=s)
    dec = jax.jit(LM.make_decode_step(cfg_ring))
    lg, _ = dec(params, caches, nxt, jnp.asarray(s, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_loss_ignores_vocab_padding():
    cfg = _tiny()
    assert cfg.vocab_padded == 256
    params = LM.init_params(KEY, cfg, dtype=jnp.float32)
    # corrupt padded unembed rows: loss must not change
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.fold_in(KEY, 1), (2, 8), 0,
                                cfg.vocab)
    l1 = LM.lm_loss(params, tokens, labels, cfg)
    params2 = dict(params)
    emb = np.asarray(params["embed"]).copy()
    emb[cfg.vocab:] = 1e3
    params2["embed"] = jnp.asarray(emb)
    l2 = LM.lm_loss(params2, tokens, labels, cfg)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_param_count_matches_init():
    for arch in ("granite-3-2b", "olmoe-1b-7b", "deepseek-v2-236b"):
        from repro import configs as configlib
        cfg = configlib.get(arch).reduced()
        params = LM.init_params(KEY, cfg, dtype=jnp.float32)
        actual = sum(x.size for x in jax.tree.leaves(params))
        expected = cfg.param_count()
        # padding of the vocab is the only allowed delta
        pad = (cfg.vocab_padded - cfg.vocab) * cfg.d_model
        if not cfg.tie_embeddings:
            pad *= 2
        assert abs(actual - expected) <= pad + 4 * cfg.d_model * cfg.n_layers
