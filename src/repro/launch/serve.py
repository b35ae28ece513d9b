"""Serving driver: continuous batched decode against a prefilled KV cache.

Container-scale it serves a smoke config on local devices (the serving example
and integration test); the full-config decode paths are proven by the dry-run.
Requests arrive with different prompt lengths; the server right-aligns prompts
into the shared ring cache (prefill), then decodes all sequences in lockstep,
emitting tokens until each hits its stop length — the standard static-batch
serving loop (continuous batching = swap finished rows for queued requests
between steps; implemented in the example).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.launch.mesh import elastic_mesh
from repro.launch.shardings import (cache_specs, ep_axes_for, param_specs,
                                    to_named)
from repro.models import lm


@dataclasses.dataclass
class ServeStats:
    prefill_s: float
    decode_s: float
    tokens: int

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / self.decode_s if self.decode_s else 0.0


def serve(arch: str, *, smoke: bool = True, batch: int = 4, prompt_len: int = 32,
          gen_len: int = 16, max_len: int = 128, mesh=None, seed: int = 0,
          params=None, greedy: bool = True):
    cfg = get_config(arch, smoke=smoke)
    mesh = mesh or elastic_mesh(len(jax.devices()),
                                model_parallel=min(2, len(jax.devices())))
    ep = ep_axes_for(mesh) if cfg.family == "moe" else ()

    with jax.set_mesh(mesh):
        if params is None:
            params = lm.init_lm(jax.random.key(seed), cfg)
        p_sh = to_named(param_specs(params, mesh, cfg), mesh)
        params = jax.device_put(params, p_sh)

        rng = np.random.default_rng(seed)
        prompts = rng.integers(0, cfg.vocab, (batch, prompt_len)).astype(np.int32)

        @jax.jit
        def prefill(params, tokens):
            cache = lm.init_cache(cfg, batch, max_len)
            logits, cache, _ = lm.forward(params, cfg, tokens=tokens,
                                          cache=cache, ep_axes=ep)
            return logits[:, -1], cache

        @jax.jit
        def decode(params, cache, tok):
            logits, cache = lm.serve_step(params, cfg, cache, tokens=tok,
                                          ep_axes=ep)
            return logits[:, -1], cache

        t0 = time.time()
        logits, cache = prefill(params, jnp.asarray(prompts))
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0

        out = []
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        t0 = time.time()
        for _ in range(gen_len):
            out.append(np.asarray(tok))
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        jax.block_until_ready(tok)
        t_decode = time.time() - t0

    gen = np.concatenate(out, axis=1)
    stats = ServeStats(t_prefill, t_decode, batch * gen_len)
    return gen, stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2.5-14b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    args = ap.parse_args()
    gen, stats = serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
                       gen_len=args.gen_len)
    print(f"[serve] generated {gen.shape} tokens; prefill {stats.prefill_s:.2f}s "
          f"decode {stats.tokens_per_s:.1f} tok/s")
    print("[serve] first row:", gen[0][:16].tolist())


if __name__ == "__main__":
    main()
