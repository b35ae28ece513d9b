"""Distributed-path tests that need multiple XLA host devices.

jax fixes the device count at first init, so these run in subprocesses with
XLA_FLAGS set (same pattern as launch/dryrun.py).  Each subprocess prints
CHECK lines that the parent asserts on.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 900) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax, jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_moe_dispatch_templates_match_local():
    """teshu / teshu2 shard_map dispatch == local math (no-drop capacity)."""
    out = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.models.config import ModelConfig, MoEConfig
        from repro.models.moe import init_moe, moe_ffn
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        for disp in ("teshu", "teshu2"):
            cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                              n_heads=2, n_kv_heads=2, d_head=16, d_ff=64,
                              vocab=64, dtype="float32", remat=False,
                              moe=MoEConfig(num_experts=8, top_k=2,
                                            d_ff_expert=32, dispatch=disp,
                                            capacity_factor=8.0))
            p = init_moe(jax.random.key(7), cfg)
            x = jax.random.normal(jax.random.key(8), (4, 16, 32))
            with jax.set_mesh(mesh):
                y_ref, _, drop_ref = moe_ffn(p, cfg, x, mesh_axes=())
                y, _, drop = jax.jit(lambda p, x: moe_ffn(
                    p, cfg, x, mesh_axes=("pod", "model")))(p, x)
            err = float(jnp.max(jnp.abs(y - y_ref)))
            same_drops = int(drop) == int(drop_ref) == 0
            print(f"CHECK {disp} err={err:.2e} ok={err < 1e-5 and same_drops}")
    """)
    assert out.count("ok=True") == 2, out


def test_chip_smoke_four_chip_phase_small():
    """chip_smoke.py --four-chip's phase at reduced width on 4 CPU devices:
    experts spread over the mesh, outputs and dropped counts match one
    device."""
    out = run_sub(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        from repro.models.config import ModelConfig, MoEConfig
        cfg = ModelConfig(name="m", family="moe", d_model=128, n_heads=2,
                          n_kv_heads=2, d_head=64, d_ff=64, vocab=64,
                          moe=MoEConfig(num_experts=16, top_k=4,
                                        d_ff_expert=64, capacity_factor=1.25,
                                        dispatch="teshu2"))
        print("CHECK", chip_smoke.four_chip_phase(0, cfg=cfg, batch=4, seq=64))
    """, devices=4)
    assert "CHECK []" in out, out
    moe = [json.loads(line) for line in out.splitlines()
           if line.startswith("{")]
    assert [r["dropped"] for r in moe if "dropped" in r][0] > 0, out


def test_hier_psum_equals_flat():
    """Network-aware gradient template == flat all-reduce numerically; int8
    compression stays within quantization error."""
    out = run_sub("""
        from repro.core import meshops
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        x = jax.random.normal(jax.random.key(0), (64, 33))

        def run(mode, compress):
            def f(v):
                return meshops.grad_sync({"g": v}, inner_axis="data",
                                         outer_axis="pod", mode=mode,
                                         compress_outer=compress)["g"]
            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=jax.P(), out_specs=jax.P(),
                check_vma=False))(x)

        flat = run("flat", False)
        hier = run("hier", False)
        comp = run("hier", True)
        e1 = float(jnp.max(jnp.abs(flat - hier)))
        rel = float(jnp.max(jnp.abs(flat - comp)) / (jnp.max(jnp.abs(flat))))
        print(f"CHECK hier_exact={e1 < 1e-4} int8_close={rel < 0.02}",
              e1, rel)
    """)
    assert "hier_exact=True" in out and "int8_close=True" in out, out


def test_embed_lookup_sharded_matches_plain():
    out = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.models.lm import _embed_lookup
        mesh = make_mesh((2, 4), ("data", "model"))
        table = jax.random.normal(jax.random.key(1), (64, 32))
        toks = jax.random.randint(jax.random.key(2), (4, 6), 0, 64)
        with jax.set_mesh(mesh):
            got = jax.jit(_embed_lookup)(table, toks)
        err = float(jnp.max(jnp.abs(got - table[toks])))
        print("CHECK", err < 1e-6)
    """)
    assert "CHECK True" in out


def test_train_step_under_mesh_runs_and_learns():
    """Two train steps on a (2,2,2) mesh with a scanned MoE smoke config."""
    out = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import Recipe, make_train_step
        from repro.launch.shardings import param_specs, to_named, ep_axes_for
        from repro.configs import get_config
        from repro.models import lm
        from repro.optim import AdamWConfig, init_opt_state

        cfg = get_config("deepseek-v2-236b", smoke=True)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        with jax.set_mesh(mesh):
            params = lm.init_lm(jax.random.key(0), cfg)
            opt = init_opt_state(params)
            step = make_train_step(cfg, AdamWConfig(lr=1e-2, warmup_steps=1,
                                                    total_steps=10),
                                   ep_axes_for(mesh), Recipe(n_micro=2))
            rng = np.random.default_rng(0)
            batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                           jnp.int32),
                     "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                           jnp.int32)}
            jstep = jax.jit(step, donate_argnums=(0, 1))
            losses = []
            for _ in range(3):
                params, opt, metrics = jstep(params, opt, batch)
                losses.append(float(metrics["loss"]))
        print("CHECK finite=", all(np.isfinite(losses)),
              "learns=", losses[-1] < losses[0], losses)
    """)
    assert "finite= True" in out and "learns= True" in out, out


def test_checkpoint_elastic_reshard():
    """Save on a (4,2) mesh, restore onto (2,2) — elastic mesh-reshape."""
    out = run_sub("""
        import tempfile
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        big = make_mesh((4, 2), ("data", "model"))
        small = make_mesh((2, 2), ("data", "model"))
        tree = {"w": jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(big, P("data", "model")))}
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d)
            cm.save(1, tree)
            target = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
            sh = {"w": NamedSharding(small, P("data", "model"))}
            restored, _ = cm.restore(target, sh)
        ok_val = bool(jnp.all(restored["w"] ==
                              jnp.arange(64, dtype=jnp.float32).reshape(8, 8)))
        ok_shard = restored["w"].sharding.mesh.shape == small.shape
        print("CHECK", ok_val and ok_shard)
    """)
    assert "CHECK True" in out


def test_train_driver_checkpoint_restart():
    """launch.train end-to-end: run 6 steps, kill, restart from step 4 —
    deterministic replay makes the loss history line up."""
    out = run_sub("""
        import tempfile, shutil
        from repro.launch.train import train
        with tempfile.TemporaryDirectory() as d:
            full = train("qwen2.5-14b", smoke=True, steps=6, global_batch=4,
                         seq_len=32, ckpt_dir=None, n_micro=1)
            part = train("qwen2.5-14b", smoke=True, steps=4, global_batch=4,
                         seq_len=32, ckpt_dir=d, ckpt_every=2, n_micro=1)
            resumed = train("qwen2.5-14b", smoke=True, steps=6, global_batch=4,
                            seq_len=32, ckpt_dir=d, ckpt_every=2, n_micro=1)
        f = [h["loss"] for h in full["history"]]
        r = [h["loss"] for h in resumed["history"]]
        # resumed covers steps 4..5; compare against the full run's tail
        err = max(abs(a - b) for a, b in zip(f[4:], r))
        print("CHECK", err < 5e-3, err, f, r)
    """, devices=4, timeout=1200)
    assert "CHECK True" in out


def test_elastic_mesh_factorizations():
    """elastic_mesh rebuilds the largest usable mesh after node loss."""
    out = run_sub("""
        from repro.launch.mesh import elastic_mesh
        m = elastic_mesh(32, model_parallel=4, pod_size=16)
        print("CHECK1", dict(m.shape))
        m2 = elastic_mesh(29, model_parallel=4, pod_size=16)   # 3 nodes lost
        print("CHECK2", dict(m2.shape))
    """, devices=32)
    assert "CHECK1 {'pod': 2, 'data': 4, 'model': 4}" in out, out
    assert "CHECK2" in out and "'model': 4" in out, out


def test_serve_driver_decodes():
    out = run_sub("""
        from repro.launch.serve import serve
        gen, stats = serve("granite-34b", smoke=True, batch=2, prompt_len=8,
                           gen_len=4, max_len=32)
        print("CHECK", gen.shape == (2, 4) and stats.tokens == 8)
    """, devices=4)
    assert "CHECK True" in out
