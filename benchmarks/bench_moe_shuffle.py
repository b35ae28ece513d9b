"""Beyond-paper integration: the shuffle layer inside an LM training step.

Two experiments, both measured from compiled HLO (loop-aware analyzer) on an
8-device (2 pod x 2 data x 2 model) mesh — on the CPU, launch with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``:

* **gradient sync**: flat all-reduce vs the network-aware hierarchical
  template (reduce-scatter inner / all-reduce outer / all-gather), with and
  without int8 cross-pod compression — DCN wire bytes per step.
* **MoE dispatch**: vanilla single-level all-to-all over (pod, model) vs the
  two-level exchange template — DCN wire bytes per dispatch.
"""
from __future__ import annotations

import numpy as np

from .common import CsvOut


def grad_sync_bytes() -> CsvOut:
    import jax
    import jax.numpy as jnp
    from repro.core import meshops
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_mesh

    out = CsvOut("grad_sync_templates",
                 ["mode", "ici_mb", "dcn_mb", "total_mb"])
    ndev = len(jax.devices())
    if ndev < 8:
        out.add(mode=f"skipped (needs 8 devices, have {ndev})",
                ici_mb=0.0, dcn_mb=0.0, total_mb=0.0)
        return out
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    grads = {"w1": jnp.ones((1024, 1024)), "w2": jnp.ones((4096, 256))}

    def run(mode, compress):
        def f(g):
            return jax.shard_map(
                lambda t: jax.tree.map(
                    lambda x: meshops.grad_sync(
                        {"g": x}, inner_axis="data",
                        outer_axis="pod", mode=mode,
                        compress_outer=compress)["g"], t),
                mesh=mesh, in_specs=jax.P(), out_specs=jax.P(),
                check_vma=False)(g)
        compiled = jax.jit(f).lower(grads).compile()
        cost = analyze_hlo(compiled.as_text(), pod_size=4)
        return cost

    for mode, compress, label in (("flat", False, "flat_allreduce"),
                                  ("hier", False, "hier_rs_ar_ag"),
                                  ("hier", True, "hier_int8_crosspod")):
        c = run(mode, compress)
        out.add(mode=label, ici_mb=c.ici_bytes / 1e6, dcn_mb=c.dcn_bytes / 1e6,
                total_mb=(c.ici_bytes + c.dcn_bytes) / 1e6)
    return out


def moe_dispatch_bytes() -> CsvOut:
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_mesh
    from repro.models.config import ModelConfig, MoEConfig
    from repro.models.moe import init_moe, moe_ffn

    out = CsvOut("moe_dispatch_templates",
                 ["dispatch", "ici_mb", "dcn_mb", "a2a_count"])
    ndev = len(jax.devices())
    if ndev < 8:
        out.add(dispatch=f"skipped (needs 8 devices, have {ndev})",
                ici_mb=0.0, dcn_mb=0.0, a2a_count=0)
        return out
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    for disp in ("teshu", "teshu2"):
        cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=256,
                          n_heads=4, n_kv_heads=4, d_head=64, d_ff=512,
                          vocab=1024, dtype="float32", remat=False,
                          moe=MoEConfig(num_experts=16, top_k=2,
                                        d_ff_expert=256, dispatch=disp,
                                        capacity_factor=1.5))
        p = init_moe(jax.random.key(0), cfg)
        x = jnp.ones((8, 128, 256))
        with jax.set_mesh(mesh):
            compiled = jax.jit(
                lambda p, x: moe_ffn(p, cfg, x,
                                     mesh_axes=("pod", "model"))[0]
            ).lower(p, x).compile()
        cost = analyze_hlo(compiled.as_text(), pod_size=4)
        a2a = sum(v for (op, _), v in cost.by_op.items() if op == "all-to-all")
        out.add(dispatch=disp, ici_mb=cost.ici_bytes / 1e6,
                dcn_mb=cost.dcn_bytes / 1e6,
                a2a_count=int(cost.collective_count))
    return out


def run() -> list[CsvOut]:
    """Both experiments in this process; with fewer than 8 devices each
    reports a skipped row (one process per chip: no child is started)."""
    return [grad_sync_bytes(), moe_dispatch_bytes()]


if __name__ == "__main__":
    for t in run():
        t.emit()
