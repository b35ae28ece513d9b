"""Pallas TPU flash attention (forward) — the prefill hot-spot of every LM arch.

Online-softmax tiling adapted to the TPU memory hierarchy: Q/K/V stream
HBM -> VMEM in (block_q × head_dim) / (block_kv × head_dim) tiles; the running
(max, sum, accumulator) state lives in VMEM scratch across the innermost kv grid
dimension; the S = QK^T and PV matmuls hit the MXU with 128-aligned shapes.

GQA is handled in the index map (kv head = q head // group) — no KV replication in
HBM.  Causal masking skips fully-masked kv tiles via ``pl.when`` (compute-skip; the
roofline perf pass measures the FLOP saving).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, block_q: int, block_kv: int,
                  kv_len: int, q_offset: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q + q_offset          # queries end-align with the kv cache
    k_start = kj * block_kv
    # causal: skip tiles strictly above the diagonal
    run = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # [bq, d]
        k = k_ref[0].astype(jnp.float32)                    # [bk, d]
        v = v_ref[0].astype(jnp.float32)                    # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        # mask kv padding beyond the true sequence length
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, _NEG_INF)

        m_prev = m_ref[:, :1]                               # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                              # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                     # [bq, 1]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "block_q", "block_kv", "interpret"))
def flash_attention(
    q: jax.Array,          # [BHq, Sq, D]
    k: jax.Array,          # [BHkv, Skv, D]
    v: jax.Array,          # [BHkv, Skv, D]
    *,
    scale: float | None = None,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = True,
) -> jax.Array:
    bhq, sq, d = q.shape
    bhkv, skv, dk = k.shape
    assert dk == d and v.shape == k.shape
    assert bhq % bhkv == 0, "q heads must be a multiple of kv heads (GQA)"
    group = bhq // bhkv
    scale = (d ** -0.5) if scale is None else scale

    # pad sequence dims to tile multiples (masked inside the kernel)
    sq_p = -(-sq // block_q) * block_q
    skv_p = -(-skv // block_kv) * block_kv
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0)))

    grid = (bhq, sq_p // block_q, skv_p // block_kv)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, kv_len=skv, q_offset=skv - sq)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq, :]
