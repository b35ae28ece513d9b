"""Training driver: checkpointed, restartable, shuffle-layer integrated.

The same loop covers two regimes:

* **container scale** — smoke configs on the local CPU devices (the end-to-end
  example and the CI integration test run this);
* **production scale** — full configs on a real mesh (the dry-run proves those
  lower/compile; this driver is what would execute them).

Fault tolerance: atomic checkpoints every ``--ckpt-every`` steps (async write),
deterministic data replay from the restored step (repro.data), restart picks up
the latest complete checkpoint, and the mesh is rebuilt from however many devices
are alive (``elastic_mesh``) — a 512-chip checkpoint restores onto 256 chips
unchanged.  Step start/end records flow through the TeShu ShuffleManager, whose
straggler detection is what a real deployment would page on.

Usage (container scale)::

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b --smoke \
        --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ARCHS, get_config
from repro.core.manager import ShuffleManager
from repro.core.plancache import PlanCache
from repro.data import DataConfig, DataPipeline
from repro.launch.mesh import batch_axes, elastic_mesh
from repro.launch.shardings import (batch_specs, ep_axes_for, param_specs,
                                    to_named)
from repro.launch.steps import Recipe, make_train_step
from repro.models import lm
from repro.optim import AdamWConfig, init_opt_state


def train(arch: str, *, smoke: bool = True, steps: int = 20,
          global_batch: int = 8, seq_len: int = 128, ckpt_dir: str | None = None,
          ckpt_every: int = 10, n_micro: int = 1, lr: float = 3e-4,
          log_every: int = 1, mesh=None, seed: int = 0) -> dict:
    cfg = get_config(arch, smoke=smoke)
    mesh = mesh or elastic_mesh(len(jax.devices()),
                                model_parallel=min(
                                    4, len(jax.devices())))
    recipe = Recipe(n_micro=n_micro, lr=lr)
    ocfg = AdamWConfig(lr=lr, total_steps=max(steps, 2),
                       warmup_steps=max(1, steps // 10),
                       moment_dtype=recipe.moment_dtype)
    ep = ep_axes_for(mesh) if cfg.family == "moe" else ()

    # The manager is the training run's shuffle control plane: the loop journals
    # step records through it, and any TeShuService attached to this manager
    # (e.g. a co-deployed data-shuffle service) shares its PlanCache.  The jit
    # step itself shuffles inside XLA, so the cache counters stay zero unless
    # such a service is wired in; they are returned for ops validation.
    manager = ShuffleManager(
        journal_path=f"{ckpt_dir}/shuffle_journal.jsonl" if ckpt_dir else None,
        plan_cache=PlanCache(capacity=64))

    with jax.set_mesh(mesh):
        params = lm.init_lm(jax.random.key(seed), cfg)
        opt_state = init_opt_state(params, recipe.moment_dtype)
        p_specs = param_specs(params, mesh, cfg)
        p_sh = to_named(p_specs, mesh)
        o_sh = {"m": p_sh, "v": p_sh,
                "step": jax.NamedSharding(mesh, jax.sharding.PartitionSpec())}
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)

        start_step = 0
        ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
        if ckpt and ckpt.latest() is not None:
            (params, opt_state), meta = ckpt.restore(
                (params, opt_state), (p_sh, o_sh))
            start_step = meta.get("step", ckpt.latest())
            print(f"[train] restored step {start_step} from {ckpt_dir}")

        dc = DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                        global_batch=global_batch, seed=seed,
                        modality=cfg.modality, d_model=cfg.d_model)
        pipe = DataPipeline(dc, mesh, start_step=start_step)

        b_sds = jax.eval_shape(lambda: pipe.dataset.batch_at(0))
        b_sh = to_named(batch_specs(b_sds, mesh), mesh)
        step_fn = jax.jit(make_train_step(cfg, ocfg, ep, recipe),
                          in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, None),
                          donate_argnums=(0, 1))

        history = []
        t0 = time.time()
        for step, batch in pipe:
            if step >= steps:
                break
            manager.record_start(0, step, "train_step")
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
            manager.record_end(0, step, "train_step")
            history.append(metrics)
            if step % log_every == 0:
                dt = (time.time() - t0) / max(1, len(history))
                print(f"[train] step={step} loss={metrics['loss']:.4f} "
                      f"gnorm={metrics['grad_norm']:.3f} "
                      f"lr={metrics['lr']:.2e} {dt*1e3:.0f}ms/step", flush=True)
            if ckpt and (step + 1) % ckpt_every == 0:
                ckpt.save_async(step + 1, (params, opt_state),
                                {"step": step + 1, "arch": arch})
        pipe.close()
        if ckpt:
            ckpt.wait()
    return {"history": history, "params": params, "opt_state": opt_state,
            "manager": manager, "plan_cache": manager.plan_cache.stats()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2.5-14b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    out = train(args.arch, smoke=args.smoke, steps=args.steps,
                global_batch=args.batch, seq_len=args.seq,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                n_micro=args.n_micro, lr=args.lr)
    losses = [h["loss"] for h in out["history"]]
    print(f"[train] done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
