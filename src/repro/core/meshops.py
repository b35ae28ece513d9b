"""Mesh-side realizations of the TeShu primitives (jax.lax collectives in shard_map).

The local-cluster backend (:mod:`primitives`) defines the semantics; this module maps
them onto a TPU mesh for the LM integrations:

* ``SEND/RECV``  -> :func:`ring_exchange` (``lax.ppermute``)
* ``PART`` + ``SEND*`` -> :func:`all_to_all_axis` / :func:`two_level_all_to_all`
* ``COMB`` (sum) -> :func:`hier_psum` — the network-aware gradient template:
  reduce-scatter over the fast intra-pod axis, (optionally int8-compressed) all-reduce
  over the slow ``pod`` axis, all-gather back.  This is Figure 3 instantiated for a
  perfect combiner (``combFunc=+`` removes ``1-1/g`` of the bytes at every level, so
  the EFF>COST test always passes — the template degenerates to the hierarchical
  schedule, chosen statically).
* ``SAMP``       -> :func:`sample_group_mask` — consistent-hash group sampling of a
  key tensor (used to estimate MoE dispatch imbalance cheaply).

All functions assume they run inside ``jax.shard_map`` with the named axes manual.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# SEND/RECV: neighbor exchange on a ring (the coordinated-template analogue)
# ---------------------------------------------------------------------------

def ring_exchange(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """SEND to (i+shift), RECV from (i-shift) along a mesh axis."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


# ---------------------------------------------------------------------------
# PART + exchange: all-to-all variants
# ---------------------------------------------------------------------------

def all_to_all_axis(x: jax.Array, axis_name: str, split_axis: int = 0,
                    concat_axis: int = 0) -> jax.Array:
    """Vanilla shuffle over one mesh axis (the baseline global dispatch)."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def two_level_all_to_all(x: jax.Array, outer_axis: str, inner_axis: str) -> jax.Array:
    """Two-level exchange [27] on a 2-D mesh slice: merge per-destination-group flows.

    ``x`` is laid out ``[outer, inner, ...]`` by destination coordinate; the result is
    ``[outer_src, inner_src, ...]`` — identical to the flat all-to-all over the
    combined ``(outer, inner)`` axis, but decomposed into a fast intra-pod stage and
    one merged flow per pod pair across the slow boundary: ``O(outer + inner)`` flows
    per chip instead of ``O(outer·inner)``, with the cross-DCN stage carrying
    contiguous per-pod aggregates (the Lambada/TeShu two-level template on a mesh).
    """
    o, i = lax.axis_size(outer_axis), lax.axis_size(inner_axis)
    assert x.shape[0] == o and x.shape[1] == i, (x.shape, o, i)
    # stage 1 (fast axis): deliver the destination-inner dimension within each pod
    y = lax.all_to_all(x, inner_axis, split_axis=1, concat_axis=1, tiled=True)
    # stage 2 (slow axis): one merged flow per pod pair delivers destination-outer
    z = lax.all_to_all(y, outer_axis, split_axis=0, concat_axis=0, tiled=True)
    return z


# ---------------------------------------------------------------------------
# COMB = sum: hierarchical / compressed gradient synchronization
# ---------------------------------------------------------------------------

def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor-row int8 quantization (rows = leading dim blocks)."""
    flat = x.reshape(-1)
    absmax = jnp.max(jnp.abs(flat)) + 1e-12
    scale = absmax / 127.0
    q = jnp.clip(jnp.round(flat / scale), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def flat_psum(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    """Vanilla shuffle with combiner: one global all-reduce (the baseline)."""
    return lax.psum(x, tuple(axis_names))


def hier_psum(
    x: jax.Array,
    inner_axis: str,
    outer_axis: str | None,
    *,
    compress_outer: bool = False,
) -> jax.Array:
    """Network-aware all-reduce: RS(inner) -> [quantize] AR(outer) [dequantize] -> AG(inner).

    Bytes crossing the slow ``outer`` boundary drop by ``1/size(inner)`` (and 4x more
    with int8 compression) versus a flat all-reduce — the mesh instantiation of the
    paper's S->R->G schedule.
    """
    n_inner = lax.axis_size(inner_axis)
    orig_shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n_inner
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = lax.psum_scatter(flat, inner_axis, scatter_dimension=0, tiled=True)
    if outer_axis is not None:
        if compress_outer:
            # int8 quantization with a pod-shared scale, accumulated in int16
            # on the wire: 2 bytes/element crossing the DCN (vs 4 for f32),
            # overflow-safe for <=256 pods (|q| <= 127 each).
            local_scale = jnp.max(jnp.abs(shard)) / 127.0 + 1e-12
            scale = lax.pmax(local_scale, outer_axis)   # shared scale -> summable ints
            q = jnp.clip(jnp.round(shard / scale), -127, 127).astype(jnp.int16)
            q = lax.psum(q, outer_axis)
            shard = q.astype(shard.dtype) * scale
        else:
            shard = lax.psum(shard, outer_axis)
    full = lax.all_gather(shard, inner_axis, axis=0, tiled=True)
    if pad:
        full = full[: full.shape[0] - pad]
    return full.reshape(orig_shape)


def grad_sync(grads, *, inner_axis: str, outer_axis: str | None, mode: str = "hier",
              compress_outer: bool = False):
    """Apply the selected gradient-shuffle plan to a grad pytree.

    ``mode``: ``flat`` (vanilla all-reduce baseline) or ``hier`` (network-aware).
    """
    axes = [a for a in (inner_axis, outer_axis) if a]
    if mode == "flat":
        return jax.tree.map(lambda g: flat_psum(g, axes), grads)
    if mode == "hier":
        return jax.tree.map(
            lambda g: hier_psum(g, inner_axis, outer_axis,
                                compress_outer=compress_outer), grads)
    raise ValueError(f"unknown grad sync mode {mode!r}")


# ---------------------------------------------------------------------------
# SAMP on the mesh: consistent-hash group masks over integer key tensors
# ---------------------------------------------------------------------------

_C1 = jnp.uint32(0x85EBCA6B)
_C2 = jnp.uint32(0xC2B2AE35)


def hash32(x: jax.Array, seed: int = 0) -> jax.Array:
    """murmur3-style finalizer; jnp analogue of messages.splitmix64 (32-bit)."""
    z = x.astype(jnp.uint32) + jnp.uint32(seed * 0x9E3779B9 + 0x9E3779B9)
    z = (z ^ (z >> 16)) * _C1
    z = (z ^ (z >> 13)) * _C2
    return z ^ (z >> 16)


def sample_group_mask(keys: jax.Array, rate: float, *, seed: int = 0) -> jax.Array:
    """Boolean mask selecting one consistent-hash destination group (Figure 4)."""
    s = max(1, int(round(1.0 / rate)))
    j = jnp.asarray(hash32(jnp.asarray([seed], jnp.int32), seed=0xC0FFEE)[0]
                    % jnp.uint32(s), jnp.uint32)
    return (hash32(keys, seed=0x5A11) % jnp.uint32(s)) == j


def estimate_tokens_per_expert(expert_ids: jax.Array, num_experts: int,
                               rate: float, *, seed: int = 0) -> jax.Array:
    """Sampled estimate of the dispatch histogram — the MoE analogue of the paper's
    reduction-ratio estimate (drives capacity/two-level decisions at run time)."""
    mask = sample_group_mask(expert_ids, rate, seed=seed)
    counts = jnp.sum(
        jax.nn.one_hot(jnp.where(mask, expert_ids, num_experts), num_experts + 1,
                       dtype=jnp.float32), axis=tuple(range(expert_ids.ndim)))[:num_experts]
    return counts / rate
