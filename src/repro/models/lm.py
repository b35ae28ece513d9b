"""The unified decoder LM covering all 10 assigned architectures.

One parameter/forward/loss/serve surface for dense, MoE (incl. MLA), SSM (xLSTM)
and hybrid (Hymba) families.  Deep uniform stacks (llama3-405b's 126 layers) are
``lax.scan``-stacked for compile-time sanity; heterogeneous stacks (xLSTM's
sLSTM/mLSTM mix, Hymba's global/SWA mix) unroll.

``train_loss`` is the train_step objective; ``serve_step`` decodes one token
against a KV/state cache (the decode_* and long_* shapes lower this, not train).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig
from .hybrid import hymba_mixer, init_hymba_block
from .layers import (Params, _dtype, attention, embed_init, init_attention,
                     init_attention_cache, init_mla, init_mla_cache, init_mlp,
                     mla_attention, mlp, rms_norm)
from .moe import init_moe, moe_ffn
from .ssm import (init_mlstm, init_mlstm_state, init_slstm, init_slstm_state,
                  mlstm_chunked, mlstm_step, slstm_forward)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, layer: int) -> Params:
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: Params = {"ln1": jnp.ones((d,), dt)}
    if cfg.family == "ssm":
        if _is_slstm(cfg, layer):
            p["slstm"] = init_slstm(ks[0], cfg)
        else:
            p["mlstm"] = init_mlstm(ks[0], cfg)
        return p
    if cfg.family == "hybrid":
        p["mixer"] = init_hymba_block(ks[0], cfg)
    elif cfg.mla is not None:
        p["attn"] = init_mla(ks[0], cfg)
    else:
        p["attn"] = init_attention(ks[0], cfg)
    p["ln2"] = jnp.ones((d,), dt)
    if cfg.family == "moe" and not _is_dense_layer(cfg, layer):
        p["moe"] = init_moe(ks[1], cfg)
    else:
        d_ff = cfg.d_ff if not _is_dense_layer(cfg, layer) or cfg.d_ff else cfg.d_ff
        p["mlp"] = init_mlp(ks[1], cfg, d_ff=d_ff or cfg.d_ff)
    return p


def _is_dense_layer(cfg: ModelConfig, layer: int) -> bool:
    """DeepSeek-style: layer 0 keeps a dense FFN; the rest are MoE."""
    return cfg.family == "moe" and cfg.moe is not None and \
        cfg.moe.num_shared > 0 and layer == 0


def _is_slstm(cfg: ModelConfig, layer: int) -> bool:
    k = cfg.ssm.slstm_every if cfg.ssm else 0
    return bool(k) and layer % k == (k - 1)


def _uniform_scan(cfg: ModelConfig) -> bool:
    return cfg.scan_layers and cfg.family in ("dense", "moe")


def init_lm(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4 + cfg.n_layers)
    dt = _dtype(cfg)
    p: Params = {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model, dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = embed_init(ks[1], cfg.vocab, cfg.d_model, dt).T
    if _uniform_scan(cfg):
        start = 1 if _is_dense_layer(cfg, 0) else 0
        if start:
            p["block0"] = _init_block(ks[2], cfg, 0)
        n_scan = cfg.n_layers - start
        stacked = jax.vmap(
            lambda k: _init_block(k, cfg, start))(jax.random.split(ks[3], n_scan))
        p["blocks"] = stacked
    else:
        p["layers"] = [_init_block(ks[4 + i], cfg, i) for i in range(cfg.n_layers)]
    return p


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _block_apply(p: Params, cfg: ModelConfig, layer: int, x, positions,
                 cache: Params | None, ep_axes: tuple[str, ...]):
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    new_cache = None
    if cfg.family == "ssm":
        if "slstm" in p:
            if cache is not None:
                out, st = slstm_forward(p["slstm"], cfg, h, cache.get("state"))
                new_cache = {"state": st}
            else:
                out, _ = slstm_forward(p["slstm"], cfg, h)
        else:
            if cache is not None and x.shape[1] == 1:
                out, st = mlstm_step(p["mlstm"], cfg, h, cache["state"])
                new_cache = {"state": st}
            else:
                # chunkwise-parallel: prefill returns the decode state for free
                out, st = mlstm_chunked(p["mlstm"], cfg, h,
                                        cache["state"] if cache else None)
                if cache is not None:
                    new_cache = {"state": st}
        return x + out, new_cache, aux
    if cfg.family == "hybrid":
        window = 0 if layer in tuple(cfg.global_attn_layers) else cfg.sliding_window
        out, mix_cache = hymba_mixer(p["mixer"], cfg, h, positions,
                                     window=window, cache=cache)
        x = x + out
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp(p["mlp"], h2)
        return x, mix_cache, aux
    # dense / moe transformer block
    if cfg.mla is not None:
        out, new_cache = mla_attention(p["attn"], cfg, h, positions, cache=cache)
    else:
        out, new_cache = attention(p["attn"], cfg, h, positions, cache=cache,
                                   window=cfg.sliding_window)
    x = x + out
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        y, aux, _ = moe_ffn(p["moe"], cfg, h2, mesh_axes=ep_axes)
        x = x + y
    else:
        x = x + mlp(p["mlp"], h2)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Forward / loss / serve
# ---------------------------------------------------------------------------

def _embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """Token-embedding gather, SPMD-safe for a d-sharded table.

    The table is sharded ``P(None, 'model')``.  Left to GSPMD, the gather's
    reshard is an "involuntary full rematerialization" that emits an invalid
    dynamic-slice at 16x16 (XLA partitioner bug).  A shard_map over ``model``
    makes it manual and trivial: each chip gathers its own d-slice, and the
    all-gather back to full D happens as an explicit, clean collective."""
    try:
        from repro.models.moe import _current_mesh
        mesh = _current_mesh()
    except Exception:
        return table[tokens]
    if "model" not in mesh.shape or table.shape[1] % mesh.shape["model"]:
        return table[tokens]
    from jax.sharding import PartitionSpec as P
    batch = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bsize = 1
    for a in batch:
        bsize *= mesh.shape[a]
    b_axes = batch if batch and tokens.shape[0] % bsize == 0 else None

    def fn(tbl, tok):                          # tbl: [V, d/model]
        x = tbl[tok]                           # local gather
        return lax.all_gather(x, "model", axis=2, tiled=True)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, "model"), P(b_axes, None)),
        out_specs=P(b_axes, None, None),
        check_vma=False,
    )(table, tokens)


def forward(params: Params, cfg: ModelConfig, *, tokens=None, embeds=None,
            positions=None, cache=None, ep_axes: tuple[str, ...] = ()):
    """Returns (logits, new_cache, aux_loss)."""
    if tokens is not None:
        x = _embed_lookup(params["embed"], tokens)
        b, s = tokens.shape
    else:
        x = embeds.astype(_dtype(cfg))
        b, s, _ = embeds.shape
    if positions is None:
        base = cache["pos"] if cache is not None else 0
        positions = base + jnp.arange(s)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))

    aux_total = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {} if cache is not None else None

    if _uniform_scan(cfg):
        start = 0
        if "block0" in params:
            c0 = None if cache is None else cache["block0"]
            x, nc0, aux = _block_apply(params["block0"], cfg, 0, x, positions,
                                       c0, ep_axes)
            aux_total += aux
            if cache is not None:
                new_cache["block0"] = nc0
            start = 1

        def body(carry, layer_in):
            xx, aux_acc = carry
            pl_, cl = layer_in
            xx, nc, aux = _block_apply(pl_, cfg, start, xx, positions, cl, ep_axes)
            return (xx, aux_acc + aux), nc

        body_fn = jax.checkpoint(body) if cfg.remat else body
        blocks_cache = None if cache is None else cache["blocks"]
        (x, aux_total), ncs = lax.scan(
            body_fn, (x, aux_total), (params["blocks"], blocks_cache))
        if cache is not None:
            new_cache["blocks"] = ncs
    else:
        for i, pl_ in enumerate(params["layers"]):
            ci = None if cache is None else cache["layers"][i]
            fn = jax.checkpoint(_block_apply, static_argnums=(1, 2, 6)) \
                if cfg.remat else _block_apply
            x, nc, aux = fn(pl_, cfg, i, x, positions, ci, ep_axes)
            aux_total += aux
            if cache is not None:
                new_cache.setdefault("layers", []).append(nc)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    unembed = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = x @ unembed
    if cache is not None:
        new_cache["pos"] = cache["pos"] + s
    return logits, new_cache, aux_total


def train_loss(params: Params, cfg: ModelConfig, batch: dict,
               ep_axes: tuple[str, ...] = ()) -> jax.Array:
    """Next-token cross-entropy (+ router aux).  ``batch``: tokens/embeds + labels."""
    logits, _, aux = forward(
        params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
        ep_axes=ep_axes)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return nll + 0.01 * aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    dt = jnp.bfloat16

    def one(layer: int):
        if cfg.family == "ssm":
            if _is_slstm(cfg, layer):
                return {"state": init_slstm_state(cfg, batch)}
            return {"state": init_mlstm_state(cfg, batch)}
        if cfg.family == "hybrid":
            return {"attn": init_attention_cache(cfg, batch, max_len, dt),
                    "ssm": {"conv": jnp.zeros((batch, cfg.ssm.conv_dim - 1,
                                               cfg.d_model * cfg.ssm.expand), dt),
                            "ssm": jnp.zeros((batch, cfg.d_model * cfg.ssm.expand,
                                              cfg.ssm.state_dim), jnp.float32)}}
        if cfg.mla is not None:
            return init_mla_cache(cfg, batch, max_len, dt)
        return init_attention_cache(cfg, batch, max_len, dt)

    cache: Params = {"pos": jnp.zeros((), jnp.int32)}
    if _uniform_scan(cfg):
        start = 0
        if _is_dense_layer(cfg, 0):
            cache["block0"] = one(0)
            start = 1
        n = cfg.n_layers - start
        cache["blocks"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one(start))
    else:
        cache["layers"] = [one(i) for i in range(cfg.n_layers)]
    return cache


def serve_step(params: Params, cfg: ModelConfig, cache: Params, tokens=None,
               embeds=None, ep_axes: tuple[str, ...] = ()):
    """Decode one token per sequence: returns (logits [B,1,V], new_cache)."""
    logits, new_cache, _ = forward(params, cfg, tokens=tokens, embeds=embeds,
                                   cache=cache, ep_axes=ep_axes)
    return logits, new_cache
