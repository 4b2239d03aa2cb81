"""Layer substrate tests: attention (flash vs reference), MoE dispatch,
Mamba-2 SSD (chunked vs recurrence vs decode), MLA decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tiny deterministic fallback (tests/_hypothesis_shim.py)
    from _hypothesis_shim import given, settings, strategies as st

from repro.configs.base import ArchConfig
from repro.layers import attention as A
from repro.layers import attn_block, mamba2, mla, moe

settings.register_profile("ci", max_examples=10, deadline=None)
settings.load_profile("ci")


@pytest.mark.slow  # heavy example sweep; fast lane keeps the decode/forward equivalence tests
@given(st.integers(1, 3), st.integers(1, 40), st.integers(1, 4),
       st.sampled_from([(4, 4), (4, 2), (8, 1)]), st.integers(0, 10 ** 6))
def test_flash_attention_matches_reference(b, t, dh_mult, heads, seed):
    h, hkv = heads
    dh = 8 * dh_mult
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, t, h, dh))
    k = jax.random.normal(ks[1], (b, t, hkv, dh))
    v = jax.random.normal(ks[2], (b, t, hkv, dh))
    for kwargs in (dict(causal=True), dict(causal=True, window=5),
                   dict(causal=False)):
        got = A.attention(q, k, v, q_chunk=7, kv_chunk=5, **kwargs)
        want = A.attention_reference(q, k, v, **kwargs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)


def test_attention_decode_with_dynamic_kv_len():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, h, hkv, dh = 2, 33, 8, 4, 16
    q = jax.random.normal(ks[0], (b, 1, h, dh))
    k = jax.random.normal(ks[1], (b, s, hkv, dh))
    v = jax.random.normal(ks[2], (b, s, hkv, dh))
    got = A.attention(q, k, v, causal=True, q_offset=20,
                      kv_len=jnp.int32(21), q_chunk=1, kv_chunk=8)
    want = A.attention_reference(q, k, v, causal=True, q_offset=20,
                                 kv_len=jnp.int32(21))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def _gqa_cfg(**kw):
    base = dict(name="t", family="dense", n_layers=1, d_model=32, vocab=64,
                n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
                q_chunk=8, kv_chunk=8)
    base.update(kw)
    return ArchConfig(**base)


def test_attn_block_decode_matches_forward():
    """Sequential decode through the KV cache == full-sequence forward."""
    cfg = _gqa_cfg()
    p, _ = attn_block.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 11, cfg.d_model)) * 0.3
    pos = jnp.broadcast_to(jnp.arange(11)[None], (2, 11))
    full = attn_block.forward(p, cfg, x, pos)
    cache = attn_block.init_cache(cfg, 2, 16)
    outs = []
    for t in range(11):
        o, cache = attn_block.decode_step(p, cfg, x[:, t:t + 1], cache,
                                          jnp.int32(t))
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_attn_block_sliding_window_ring_buffer():
    cfg = _gqa_cfg(sliding_window=4)
    p, _ = attn_block.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 13, cfg.d_model)) * 0.3
    pos = jnp.broadcast_to(jnp.arange(13)[None], (1, 13))
    full = attn_block.forward(p, cfg, x, pos)  # windowed full-seq
    cache = attn_block.init_cache(cfg, 1, 13)
    assert cache["k"].shape[1] == 4  # ring bounded by the window
    outs = []
    for t in range(13):
        o, cache = attn_block.decode_step(p, cfg, x[:, t:t + 1], cache,
                                          jnp.int32(t))
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_moe_matches_dense_reference_no_drops():
    p, _ = moe.init(jax.random.PRNGKey(0), 32, 64, n_experts=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    y, aux = moe.forward(p, x, top_k=2, capacity_factor=8.0)
    yr = moe.forward_dense_reference(p, x, top_k=2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)
    assert float(aux) > 0


@pytest.mark.slow  # heavy example sweep; test_moe_matches_dense_reference stays fast
@given(st.integers(1, 64), st.integers(2, 8), st.integers(1, 4),
       st.integers(0, 10 ** 6))
def test_moe_dispatch_table_invariants(t, e, k, seed):
    """Sort-free dispatch: every kept slot lands in its expert's segment
    at a unique position below capacity; drops only past capacity."""
    k = min(k, e)
    cap = max(2, t * k // e)
    topk_e = jax.random.randint(jax.random.PRNGKey(seed), (t, k), 0, e)
    table, valid, slot = moe.dispatch_tables(topk_e, e, cap)
    table = np.asarray(table)
    valid = np.asarray(valid)
    slot = np.asarray(slot)
    flat_e = np.asarray(topk_e).reshape(-1)
    # kept slots: slot // cap == expert id and slots are unique
    kept = slot < e * cap
    assert len(np.unique(slot[kept])) == kept.sum()
    assert (slot[kept] // cap == flat_e[kept]).all()
    # per-expert kept count == min(arrivals, capacity)
    for ex in range(e):
        arrivals = (flat_e == ex).sum()
        assert (valid.reshape(e, cap)[ex]).sum() == min(arrivals, cap)


def test_moe_shared_experts():
    p, _ = moe.init(jax.random.PRNGKey(0), 32, 64, n_experts=4, n_shared=2,
                    shared_d_ff=48)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    y, _ = moe.forward(p, x, top_k=2, capacity_factor=8.0)
    yr = moe.forward_dense_reference(p, x, top_k=2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


class _SsmCfg:
    d_model = 32
    ssm_expand = 2
    ssm_headdim = 8
    ssm_state = 16
    ssm_conv = 4
    norm_eps = 1e-5
    ssd_precision = "highest"


def test_ssd_chunked_vs_recurrence_vs_decode():
    cfg = _SsmCfg()
    p, _ = mamba2.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 23, 32)) * 0.5
    ref = mamba2.forward_reference(p, cfg, x)
    chunked = mamba2.forward(p, cfg, x, chunk=8)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    cache = mamba2.init_cache(cfg, 2)
    outs = []
    for t in range(23):
        o, cache = mamba2.decode_step(p, cfg, x[:, t:t + 1], cache)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # heavy example sweep; chunked-vs-recurrence equivalence stays fast
@given(st.sampled_from([4, 8, 16, 32]), st.integers(0, 10 ** 6))
def test_ssd_chunk_size_invariance(chunk, seed):
    cfg = _SsmCfg()
    p, _ = mamba2.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 17, 32)) * 0.5
    a = mamba2.forward(p, cfg, x, chunk=chunk)
    b = mamba2.forward(p, cfg, x, chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


class _MlaCfg:
    d_model = 32
    n_heads = 4
    q_lora_rank = 0
    kv_lora_rank = 16
    qk_nope_head_dim = 8
    qk_rope_head_dim = 4
    v_head_dim = 8
    rope_theta = 10000.0
    sliding_window = None


def test_mla_decode_matches_forward():
    cfg = _MlaCfg()
    p, _ = mla.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 32)) * 0.3
    pos = jnp.broadcast_to(jnp.arange(9)[None], (2, 9))
    full = mla.forward(p, cfg, x, pos)
    cache = mla.init_cache(cfg, 2, 12)
    # the MLA cache is the compressed latent, not per-head K/V
    assert cache["c_kv"].shape == (2, 12, cfg.kv_lora_rank)
    outs = []
    for t in range(9):
        o, cache = mla.decode_step(p, cfg, x[:, t:t + 1], cache, jnp.int32(t))
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(full), rtol=2e-4, atol=2e-4)
