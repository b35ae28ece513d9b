"""Substrate tests: data pipeline, optimizer, checkpointing, HLO analyzer."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.checkpoint import CheckpointManager, latest_step, save_checkpoint
from repro.data import DataConfig, DataPipeline, SyntheticLMDataset
from repro.optim import (AdamWConfig, adamw_update, clip_by_global_norm,
                         cosine_schedule, global_norm, init_opt_state,
                         microbatch_grads)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_dataset_determinism_and_shapes():
    dc = DataConfig(vocab=128, seq_len=16, global_batch=4)
    ds = SyntheticLMDataset(dc)
    a, b = ds.batch_at(3), ds.batch_at(3)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].shape == (4, 16)
    assert a["labels"].shape == (4, 16)
    # labels are next-token shifted
    full = SyntheticLMDataset(dc).batch_at(3)
    assert not np.array_equal(full["tokens"], full["labels"])


def test_dataset_embeds_modality():
    dc = DataConfig(vocab=64, seq_len=8, global_batch=2, modality="vlm",
                    d_model=32)
    b = SyntheticLMDataset(dc).batch_at(0)
    assert b["embeds"].shape == (2, 8, 32)
    assert "tokens" not in b


def test_pipeline_replay_from_step():
    """Restart replay: pipeline(start_step=k) yields the same batch k."""
    dc = DataConfig(vocab=128, seq_len=16, global_batch=2)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    p1 = DataPipeline(dc, mesh, start_step=0)
    it = iter(p1)
    batches = {s: np.asarray(b["tokens"]) for s, b in
               (next(it) for _ in range(4))}
    p1.close()
    p2 = DataPipeline(dc, mesh, start_step=2)
    it2 = iter(p2)
    s, b = next(it2)
    p2.close()
    assert s == 2
    assert np.array_equal(np.asarray(b["tokens"]), batches[2])


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_frac=0.1)
    assert float(cosine_schedule(cfg, jnp.int32(0))) == 0.0
    assert float(cosine_schedule(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(cosine_schedule(cfg, jnp.int32(100))) == pytest.approx(0.1)
    mid = float(cosine_schedule(cfg, jnp.int32(55)))
    assert 0.1 < mid < 1.0


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(90 + 160))
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_adamw_decay_mask_skips_norms():
    params = {"w": jnp.ones((4, 4)), "ln1": jnp.ones((4,))}
    grads = {"w": jnp.zeros((4, 4)), "ln1": jnp.zeros((4,))}
    cfg = AdamWConfig(lr=0.1, weight_decay=0.5, warmup_steps=0, total_steps=1,
                      grad_clip=1e9)
    p2, _, _ = adamw_update(cfg, params, grads, init_opt_state(params))
    assert float(jnp.max(jnp.abs(p2["ln1"] - 1.0))) == 0.0   # no decay on norms
    assert float(jnp.max(jnp.abs(p2["w"] - 1.0))) > 0.0      # decay on matrices


def test_microbatch_grads_match_full_batch():
    w = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(8, 8)),
                          jnp.float32)}
    x = jnp.asarray(np.random.default_rng(1).normal(size=(16, 8)), jnp.float32)

    def loss(p, batch):
        return jnp.mean((batch["x"] @ p["w"]) ** 2)

    l1, g1 = microbatch_grads(loss, w, {"x": x}, 1)
    l4, g4 = microbatch_grads(loss, w, {"x": x}, 4)
    assert float(l1) == pytest.approx(float(l4), rel=1e-6)
    np.testing.assert_allclose(g1["w"], g4["w"], rtol=1e-5)


def test_bf16_moments_update_works():
    params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    grads = {"w": jnp.full((4, 4), 0.1, jnp.bfloat16)}
    st = init_opt_state(params, "bfloat16")
    assert st["m"]["w"].dtype == jnp.bfloat16
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=1)
    p2, st2, _ = adamw_update(cfg, params, grads, st)
    assert p2["w"].dtype == jnp.bfloat16
    assert st2["m"]["w"].dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(p2["w"].astype(jnp.float32) - 1.0))) > 0


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def _tree():
    return {"a": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.float32),
                  "d": jnp.zeros((), jnp.int32)}}


def test_checkpoint_roundtrip_bf16(tmp_path):
    tree = _tree()
    save_checkpoint(str(tmp_path), 5, tree, {"note": "hi"})
    cm = CheckpointManager(str(tmp_path))
    restored, meta = cm.restore(tree)
    assert meta["note"] == "hi"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        assert bool(jnp.all(a == b))


def test_checkpoint_atomicity_ignores_partial(tmp_path):
    tree = _tree()
    save_checkpoint(str(tmp_path), 1, tree)
    # simulate a crash mid-save: partial tmp dir without manifest
    os.makedirs(tmp_path / "step_00000002.tmp-999")
    (tmp_path / "step_00000002.tmp-999" / "arr_00000.npy").write_bytes(b"junk")
    assert latest_step(str(tmp_path)) == 1


def test_checkpoint_retention(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        cm.save(s, _tree())
    assert latest_step(str(tmp_path)) == 4
    steps = sorted(os.listdir(tmp_path))
    assert steps == ["step_00000003", "step_00000004"]


def test_checkpoint_async(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    tree = _tree()
    cm.save_async(7, tree)
    cm.wait()
    restored, _ = cm.restore(tree)
    assert bool(jnp.all(restored["a"] == tree["a"]))
    assert restored["a"].dtype == jnp.bfloat16


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, _tree())
    bad = _tree()
    bad["a"] = jnp.zeros((2, 2), jnp.bfloat16)
    cm = CheckpointManager(str(tmp_path))
    with pytest.raises(ValueError, match="shape mismatch"):
        cm.restore(bad)


# ---------------------------------------------------------------------------
# HLO analyzer unit tests (the roofline's measurement tool)
# ---------------------------------------------------------------------------

def test_roofline_peaks_are_keyed_by_device_kind():
    from repro.launch.roofline import peaks_for
    assert peaks_for("TPU v5 lite").flops == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("cpu")


def test_hlo_analyzer_scales_loops():
    from repro.launch.hlo_analysis import analyze_hlo
    from jax import lax

    def f(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = lax.scan(body, x, None, length=9)
        return out

    w = jnp.zeros((32, 32))
    x = jnp.zeros((4, 32))
    compiled = jax.jit(f).lower(w, x).compile()
    cost = analyze_hlo(compiled.as_text())
    assert cost.flops == pytest.approx(2 * 4 * 32 * 32 * 9, rel=1e-6)


def test_hlo_analyzer_dot_flops_batched():
    from repro.launch.hlo_analysis import analyze_hlo

    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jnp.zeros((3, 8, 16))
    b = jnp.zeros((3, 16, 4))
    compiled = jax.jit(f).lower(a, b).compile()
    cost = analyze_hlo(compiled.as_text())
    assert cost.flops == pytest.approx(2 * 3 * 8 * 4 * 16, rel=1e-6)


def test_hlo_analyzer_group_parsing():
    from repro.launch.hlo_analysis import _iota_groups
    g = _iota_groups("[8,8]<=[8,8]T(1,0)")
    assert g.shape == (8, 8)
    # T(1,0) on an [8,8] iota: groups stride across the fast axis
    assert g[0, 1] - g[0, 0] == 8
