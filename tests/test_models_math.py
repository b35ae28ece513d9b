"""Mathematical correctness of the model substrate: chunked forms vs exact
recurrences, blocked attention vs fused, MoE dispatch equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.blocked_attention import blocked_attention
from repro.models.config import ModelConfig, MoEConfig, SSMConfig
from repro.models.hybrid import init_mamba, mamba_forward
from repro.models.layers import _sdpa_fused
from repro.models.ssm import (init_mlstm, init_mlstm_state, mlstm_chunked,
                              mlstm_step)


def _ssm_cfg(d=32, h=4):
    return ModelConfig(name="t", family="ssm", n_layers=1, d_model=d, n_heads=h,
                       n_kv_heads=h, d_head=d // h, d_ff=0, vocab=64,
                       dtype="float32", remat=False, ssm=SSMConfig())


# ---------------------------------------------------------------------------
# mLSTM: chunked == step recurrence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 16, 37, 64])
def test_mlstm_chunked_matches_recurrence(chunk):
    cfg = _ssm_cfg()
    p = init_mlstm(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 37, 32))
    st = init_mlstm_state(cfg, 2)
    outs = []
    for t in range(37):
        o, st = mlstm_step(p, cfg, x[:, t:t + 1], st)
        outs.append(o)
    o_seq = jnp.concatenate(outs, axis=1)
    o_chunk, st_c = mlstm_chunked(p, cfg, x, chunk=chunk)
    np.testing.assert_allclose(o_chunk, o_seq, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(st_c["C"], st["C"], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(st_c["n"], st["n"], rtol=1e-4, atol=1e-5)


def test_mlstm_split_resume():
    """Chunked with carried state == one continuous pass (prefill resume)."""
    cfg = _ssm_cfg()
    p = init_mlstm(jax.random.key(2), cfg)
    x = jax.random.normal(jax.random.key(3), (1, 40, 32))
    o_full, _ = mlstm_chunked(p, cfg, x, chunk=8)
    o_a, st = mlstm_chunked(p, cfg, x[:, :24], chunk=8)
    o_b, _ = mlstm_chunked(p, cfg, x[:, 24:], st, chunk=8)
    np.testing.assert_allclose(jnp.concatenate([o_a, o_b], 1), o_full,
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# mamba: chunked == full associative scan; decode == chunked tail
# ---------------------------------------------------------------------------

def test_mamba_chunked_invariance():
    cfg = ModelConfig(name="h", family="hybrid", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=2, d_head=8, d_ff=64, vocab=64,
                      dtype="float32", remat=False,
                      ssm=SSMConfig(state_dim=8, conv_dim=4, expand=2))
    p = init_mamba(jax.random.key(4), cfg)
    x = jax.random.normal(jax.random.key(5), (2, 53, 32))
    y_ref, s_ref = mamba_forward(p, cfg, x, chunk=64)    # single chunk
    for chunk in (8, 16, 32):
        y, s = mamba_forward(p, cfg, x, chunk=chunk)
        np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(s["ssm"], s_ref["ssm"], rtol=1e-4, atol=1e-6)
    # decode continuation matches the full pass
    y_pre, s_pre = mamba_forward(p, cfg, x[:, :52], chunk=16)
    y_tok, _ = mamba_forward(p, cfg, x[:, 52:], state=s_pre)
    np.testing.assert_allclose(y_tok, y_ref[:, 52:], rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# blocked attention == fused attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,block_kv", [(5, 8), (9, 32), (16, 16),
                                             (33, 8)])
def test_windowed_kv_restriction(window, block_kv):
    """The sliding-window kv-block slice path == full-scan masking, across
    window/block alignments (exercises the dynamic_slice fast path)."""
    q = jax.random.normal(jax.random.key(20), (1, 64, 4, 16))
    k = jax.random.normal(jax.random.key(21), (1, 64, 2, 16))
    v = jax.random.normal(jax.random.key(22), (1, 64, 2, 16))
    got = blocked_attention(q, k, v, causal=True, window=window,
                            block_q=16, block_kv=block_kv)
    expect = _sdpa_fused(q, k, v, causal=True, window=window, q_offset=0,
                         valid_len=None)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kw", [
    dict(causal=True),
    dict(causal=True, q_offset=20),
    dict(causal=True, window=9, q_offset=20),
    dict(causal=True, q_offset=20, valid_len=60),
])
def test_blocked_attention_matches_fused(kw):
    q = jax.random.normal(jax.random.key(6), (2, 50, 8, 16))
    k = jax.random.normal(jax.random.key(7), (2, 70, 2, 16))
    v = jax.random.normal(jax.random.key(8), (2, 70, 2, 24))   # dv != dk (MLA)
    o1 = blocked_attention(q, k, v, block_q=16, block_kv=32, **kw)
    o2 = _sdpa_fused(q, k, v, causal=True, window=kw.get("window", 0),
                     q_offset=kw.get("q_offset", 0),
                     valid_len=kw.get("valid_len"))
    np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# MoE: shard_map dispatch templates == local reference (no-drop capacity)
# ---------------------------------------------------------------------------

def test_moe_dispatch_templates_equivalent():
    from repro.models.moe import init_moe, moe_ffn
    if len(jax.devices()) < 8:
        devs = len(jax.devices())
        pytest.skip(f"needs 8 local devices, have {devs}")


def test_moe_gspmd_math():
    """Routing + capacity + combine math, no mesh: weighted expert mixture."""
    from repro.models.moe import init_moe, moe_ffn
    cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_head=8, d_ff=32, vocab=64,
                      dtype="float32", remat=False,
                      moe=MoEConfig(num_experts=4, top_k=4, d_ff_expert=16,
                                    capacity_factor=8.0))
    p = init_moe(jax.random.key(9), cfg)
    x = jax.random.normal(jax.random.key(10), (1, 6, 16))
    y, aux, dropped = moe_ffn(p, cfg, x)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(aux))
    assert int(dropped) == 0
    # top_k == num_experts with huge capacity: output == full softmax mixture
    logits = (x.reshape(-1, 16) @ p["router"]).astype(jnp.float32)
    w = jax.nn.softmax(logits, -1)
    def ffn(e, xx):
        h = jax.nn.silu(xx @ p["experts"]["w_gate"][e]) * \
            (xx @ p["experts"]["w_up"][e])
        return h @ p["experts"]["w_down"][e]
    expect = sum(w[:, e:e + 1] * ffn(e, x.reshape(-1, 16)) for e in range(4))
    np.testing.assert_allclose(y.reshape(-1, 16), expect.reshape(-1, 16),
                               rtol=1e-4, atol=1e-5)
