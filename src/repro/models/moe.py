"""Mixture-of-Experts with the shuffle layer as a first-class dispatch service.

MoE token dispatch **is** a TeShu shuffle: ``partFunc`` = router top-k, the transfer
crosses the expert-parallel mesh axes, and the combine applies routing weights.
Three dispatch templates are selectable per config (`cfg.moe.dispatch`):

* ``gspmd``  — vanilla shuffling: build the per-expert buffers under GSPMD sharding
  constraints and let XLA insert the collectives (the baseline).
* ``teshu``  — explicit shard_map dispatch: one flat ``all_to_all`` over the EP axes
  (``('pod','model')`` when multi-pod), the mesh analogue of the vanilla template
  executed through the shuffle layer.
* ``teshu2`` — the two-level exchange template [27]: stage the all-to-all over the
  fast ``model`` axis first, then one merged flow per pod pair across the DCN —
  the paper's hierarchical optimization applied to MoE dispatch.

Routing uses fixed per-expert capacity (tokens over capacity drop, standard MoE
semantics); ``meshops.estimate_tokens_per_expert`` is the SAMP hook that sizes
capacity adaptively from a cheap sampled histogram.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import meshops

from .config import ModelConfig
from .layers import Params, dense_init, _dtype


def init_moe(key, cfg: ModelConfig) -> Params:
    m = cfg.moe
    dt = _dtype(cfg)
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts

    def expert_stack(k, n):
        kk = jax.random.split(k, 3)
        return {"w_gate": dense_init(kk[0], d, f, dt)[None].repeat(n, 0) * 1.0,
                "w_up": dense_init(kk[1], d, f, dt)[None].repeat(n, 0) * 1.0,
                "w_down": dense_init(kk[2], f, d, dt)[None].repeat(n, 0) * 1.0}

    p = {"router": dense_init(ks[0], d, e, dt, scale=0.02),
         "experts": expert_stack(ks[1], e)}
    if m.num_shared:
        p["shared"] = expert_stack(ks[2], m.num_shared)
    return p


def _expert_ffn(w: Params, x: jax.Array) -> jax.Array:
    """x: [E, C, d]; w[*]: [E, d, f] / [E, f, d]."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, w["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", x, w["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, w["w_down"]).astype(x.dtype)


def _route(router_w, x_flat, m):
    """partFunc: top-k expert assignment + normalized routing weights + aux loss.

    The aux term is the standard load-balance loss (Switch/GShard):
    ``E * sum_e f_e * P_e`` where ``f_e`` is the fraction of tokens whose top-1
    choice is ``e`` and ``P_e`` the mean router probability of ``e``."""
    logits = (x_flat @ router_w).astype(jnp.float32)            # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, eids = lax.top_k(probs, m.top_k)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-9)
    f = jnp.mean(jax.nn.one_hot(eids[:, 0], m.num_experts, dtype=jnp.float32), axis=0)
    p_mean = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(f * p_mean)
    return eids.astype(jnp.int32), weights, aux                  # [T, k], [T, k], []


def _build_buffers(x_flat, eids, weights, num_experts, cap):
    """Scatter tokens into fixed-capacity per-expert buffers (PART primitive).

    Returns (buf [E, cap, d], wbuf [E, cap], gather indices for the combine)."""
    t, d = x_flat.shape
    k = eids.shape[1]
    flat_e = eids.reshape(-1)                                    # [T*k]
    flat_w = weights.reshape(-1)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(t * k), flat_e]
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, num_experts * cap)
    buf = jnp.zeros((num_experts * cap + 1, d), x_flat.dtype)
    buf = buf.at[slot].set(x_flat[tok], mode="drop")[:-1].reshape(num_experts, cap, d)
    wbuf = jnp.zeros((num_experts * cap + 1,), flat_w.dtype)
    wbuf = wbuf.at[slot].set(flat_w, mode="drop")[:-1].reshape(num_experts, cap)
    return buf, wbuf, (slot, keep, tok)


def _combine(out_buf, wbuf, meta, t, d):
    """COMB: weighted gather of expert outputs back to source tokens."""
    slot, keep, tok = meta
    flat = (out_buf * wbuf[..., None]).reshape(-1, d)
    flat = jnp.concatenate([flat, jnp.zeros((1, d), flat.dtype)], axis=0)
    y = flat[jnp.minimum(slot, flat.shape[0] - 1)]
    y = jnp.where(keep[:, None], y, 0.0)
    out = jnp.zeros((t, d), out_buf.dtype).at[tok].add(y.astype(out_buf.dtype))
    return out


def moe_ffn(p: Params, cfg: ModelConfig, x: jax.Array, *,
            mesh_axes: tuple[str, ...] = ()
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, S, D] -> ([B, S, D], aux loss, dropped).  ``mesh_axes`` = EP
    mesh axes; ``dropped`` counts the (token, expert) assignments that found
    their expert's buffer full, over the whole batch."""
    m = cfg.moe
    b, s, d = x.shape
    out = jnp.zeros_like(x)
    if m.num_shared:
        xs = x.reshape(1, b * s, d)
        shared = _expert_ffn(p["shared"],
                             jnp.broadcast_to(xs, (m.num_shared, b * s, d)))
        out += jnp.sum(shared, axis=0).reshape(b, s, d)

    dispatch = m.dispatch if mesh_axes else "gspmd"
    if dispatch == "gspmd" or not mesh_axes:
        y, aux, dropped = _moe_gspmd(p, cfg, x, mesh_axes)
    else:
        y, aux, dropped = _moe_shard_map(p, cfg, x, mesh_axes,
                                         two_level=(dispatch == "teshu2"))
    return out + y, aux, dropped


# ---------------------------------------------------------------------------
# Baseline: vanilla shuffle under GSPMD
# ---------------------------------------------------------------------------

def _moe_gspmd(p: Params, cfg: ModelConfig, x: jax.Array,
               mesh_axes: tuple[str, ...]
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    m = cfg.moe
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    eids, weights, aux = _route(p["router"], x_flat, m)
    cap = _capacity(b * s, m)
    buf, wbuf, meta = _build_buffers(x_flat, eids, weights, m.num_experts, cap)
    if mesh_axes:
        spec = P(mesh_axes, None, None)
        buf = lax.with_sharding_constraint(buf, spec)
    y = _expert_ffn(p["experts"], buf)
    if mesh_axes:
        y = lax.with_sharding_constraint(y, P(mesh_axes, None, None))
    dropped = jnp.sum(~meta[1], dtype=jnp.int32)
    return _combine(y, wbuf, meta, b * s, d).reshape(b, s, d), aux, dropped


# ---------------------------------------------------------------------------
# TeShu: explicit shard_map dispatch (vanilla or two-level template)
# ---------------------------------------------------------------------------

def _capacity(tokens: int, m) -> int:
    cap = int(tokens * m.top_k * m.capacity_factor / m.num_experts) + 1
    return max(8, -(-cap // 8) * 8)


def _moe_shard_map(p: Params, cfg: ModelConfig, x: jax.Array,
                   ep_axes: tuple[str, ...], *, two_level: bool
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Explicit expert-parallel dispatch through the shuffle layer.

    Geometry: tokens stay sharded over the batch axes ``('pod','data')``; experts
    are sharded over ``ep_axes`` (``('model',)`` single-pod, ``('pod','model')``
    multi-pod) and replicated over ``data``.  For a fixed ``data`` coordinate the
    chips spanning ``ep_axes`` form one EP group covering every expert; the shuffle
    is an all-to-all over exactly those axes.  Work division: each ``model``
    coordinate routes a distinct slice of its chip's tokens (they are replicated
    over ``model``), and an all-gather over ``model`` restores the full activation.
    """
    m = cfg.moe
    mesh = _current_mesh()
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    e_total = m.num_experts
    ep = 1
    for a in ep_axes:
        ep *= mesh.shape[a]
    assert e_total % ep == 0, (e_total, ep)
    e_local = e_total // ep
    msize = mesh.shape["model"]

    def fn(x_blk, router_w, experts):
        bl, s, d = x_blk.shape
        tokens = bl * s
        do_slice = tokens % msize == 0 and tokens >= msize
        if do_slice:                         # divide routing work over 'model'
            tl = tokens // msize
            x_my = lax.dynamic_slice_in_dim(
                x_blk.reshape(tokens, d), lax.axis_index("model") * tl, tl, 0)
        else:                                # tiny (decode) batches: route all
            tl = tokens
            x_my = x_blk.reshape(tokens, d)
        eids, weights, aux = _route(router_w, x_my, m)
        cap = _capacity(tl, m)
        buf, wbuf, meta = _build_buffers(x_my, eids, weights, e_total, cap)
        # shuffle template: deliver per-expert buffers to their shards
        payload = jnp.concatenate(
            [buf, wbuf[..., None].astype(buf.dtype)], axis=-1
        ).reshape(ep, e_local * cap, d + 1)
        payload = _ep_shuffle(payload, ep_axes, mesh, two_level)
        xb = payload[..., :d].reshape(ep, e_local, cap, d)
        wb = payload[..., d].reshape(ep, e_local, cap)
        # my local experts applied to tokens from every EP-group source chip
        xb = xb.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, d)
        mask = (wb.transpose(1, 0, 2).reshape(e_local, ep * cap) > 0)
        yb = _expert_ffn(experts, xb)        # experts arrive pre-sliced: [e_local,...]
        yb = jnp.where(mask[..., None], yb, 0.0)
        # reverse shuffle: outputs back to source chips, same slot layout
        yb = yb.reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3).reshape(
            ep, e_local * cap, d)
        yb = _ep_shuffle(yb, ep_axes, mesh, two_level)
        y = _combine(yb.reshape(e_total, cap, d), wbuf, meta, tl, d)
        if do_slice:
            y = lax.all_gather(y, "model", axis=0, tiled=True)
        aux = lax.pmean(aux, tuple(a for a in ("pod", "data", "model")
                                   if a in mesh.shape))
        # every batch shard routes its own tokens, and so does every model
        # coordinate when the routing work is sliced over 'model'
        dropped = jnp.sum(~meta[1], dtype=jnp.int32)
        owners = batch_axes + (("model",) if do_slice else ())
        if owners:
            dropped = lax.psum(dropped, owners)
        return y.reshape(bl, s, d), aux, dropped

    batch_spec = P(batch_axes if batch_axes else None, None, None)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(batch_spec, P(), P(ep_axes, None, None)),
        out_specs=(batch_spec, P(), P()),
        check_vma=False,
    )(x, p["router"], p["experts"])


def _ep_shuffle(x: jax.Array, ep_axes: tuple[str, ...], mesh, two_level: bool):
    """The dispatch shuffle: flat all-to-all (vanilla template) or the two-level
    exchange template over (slow pod boundary, fast model axis)."""
    if two_level and len(ep_axes) == 2:
        o, i = mesh.shape[ep_axes[0]], mesh.shape[ep_axes[1]]
        return meshops.two_level_all_to_all(
            x.reshape(o, i, *x.shape[1:]), ep_axes[0], ep_axes[1]
        ).reshape(x.shape)
    return lax.all_to_all(x, ep_axes, split_axis=0, concat_axis=0, tiled=True)


def _current_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        raise RuntimeError("moe shard_map dispatch requires an active mesh "
                           "(run under `with jax.set_mesh(mesh):`)")
    return mesh
