"""Pallas TPU decode attention — one new token against a deep KV cache.

The decode hot-spot: q is [B, H, d] (a single position), the cache is
[B, T, KVH, d] with T up to 512k.  Per (batch, kv-head) grid cell the q rows are
that kv head's GQA group (group = H/KVH rows — up to 48 for MQA), streamed against
kv tiles with the same online-softmax state as the prefill kernel, but the state
is tiny ([group, d]) and the kv tiles dominate: this kernel is memory-bound by
design, its roofline is the HBM stream of the cache.

``valid_len`` masks unwritten cache tail (ring-buffer decode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_KV = 512
_NEG = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   scale: float, block_kv: int):
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = len_ref[0]
    start = kj * block_kv

    @pl.when(start < valid)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # [g, d]
        k = k_ref[0, :, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, :, 0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < valid, s, _NEG)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(kj == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_kv", "interpret"))
def decode_attention(
    q: jax.Array,          # [B, H, d] one token per sequence
    k: jax.Array,          # [B, T, KVH, d]
    v: jax.Array,          # [B, T, KVH, d]
    valid_len: jax.Array,  # [] int32 — filled cache length (causal bound incl. q)
    *,
    scale: float | None = None,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = True,
) -> jax.Array:
    b, h, d = q.shape
    _, t, kvh, dk = k.shape
    assert dk == d and v.shape == k.shape and h % kvh == 0
    g = h // kvh
    scale = (d ** -0.5) if scale is None else scale

    t_p = -(-t // block_kv) * block_kv
    if t_p != t:
        k = jnp.pad(k, ((0, 0), (0, t_p - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_p - t), (0, 0), (0, 0)))
    qg = q.reshape(b * kvh, g, d)                          # one row-block per kv head

    grid = (b, kvh, t_p // block_kv)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_kv=block_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, g, d), lambda bb, hh, jj, ln: (bb * pl.num_programs(1) + hh, 0, 0)),
                pl.BlockSpec((1, block_kv, 1, d), lambda bb, hh, jj, ln: (bb, jj, hh, 0)),
                pl.BlockSpec((1, block_kv, 1, d), lambda bb, hh, jj, ln: (bb, jj, hh, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, g, d), lambda bb, hh, jj, ln: (bb * pl.num_programs(1) + hh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g, 128), jnp.float32),
                pltpu.VMEM((g, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * kvh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(valid_len, jnp.int32).reshape(1), qg, k, v)
    return out.reshape(b, h, d)
