import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
"""Perf-iteration harness (§Perf): lower one cell with knob overrides, print
the roofline terms and the top byte/flop contributors.

    PYTHONPATH=src python -m repro.launch.perf --arch llama3-405b \
        --shape train_4k [--multi-pod] [--n-micro 8] [--block-kv 4096] \
        [--dispatch teshu] [--no-remat] [--top 12]

Each invocation = one hypothesis test: change a knob, re-lower, diff the terms.
"""
import argparse
import json

import jax

from repro.configs import ARCHS, SHAPES
from repro.launch import hlo_analysis as H
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch.roofline import analyze
from repro.launch.steps import Recipe, build_cell, recipe_for


def top_items(an: H.HloAnalyzer, n: int = 12):
    items = []

    def walk(name, mult):
        comp = an.comps.get(name)
        if comp is None:
            return
        for instr in comp.instrs:
            if instr.op == "while":
                trips = H._trip_count(instr.line)
                body = H._called(instr.line, "body")
                if body:
                    walk(body, mult * trips)
                continue
            if instr.op == "call":
                t = H._called(instr.line, "to_apply")
                if t:
                    walk(t, mult)
                continue
            if instr.op in H._SKIP_BYTES_OPS or instr.op.endswith("-done"):
                continue
            b = an._instr_bytes(instr, comp)
            flash = "flash_xla" in instr.line
            items.append((b * mult, mult, instr.op, instr.name, flash))

    walk(an.entry, 1.0)
    items.sort(reverse=True)
    return items[:n]


def run(arch: str, shape: str, *, multi_pod: bool, recipe: Recipe,
        block_q=None, block_kv=None, top: int = 12, label: str = "") -> dict:
    from repro.models.blocked_attention import set_block_defaults
    set_block_defaults(block_q, block_kv)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_cell(arch, shape, mesh, recipe=recipe)
    with jax.set_mesh(mesh):
        compiled = cell.lower().compile()
    roof = analyze(compiled, arch=arch, shape=SHAPES[shape], mesh=mesh,
                   cfg=cell.cfg,
                   device_kind=PRODUCTION_DEVICE_KIND)
    row = roof.row()
    print(f"\n=== {label or 'cell'}: {arch} x {shape} on {row['mesh']} ===")
    print(f"  compute    {roof.compute_s*1e3:12.1f} ms")
    print(f"  memory     {roof.memory_s*1e3:12.1f} ms   "
          f"(kernel-adjusted {roof.memory_s_kernel*1e3:.1f} ms)")
    print(f"  collective {roof.collective_s*1e3:12.1f} ms   "
          f"(ici {row['ici_gb']:.1f} GB, dcn {row['dcn_gb']:.2f} GB per chip)")
    print(f"  dominant={roof.dominant}  mfu={roof.mfu:.3f}  "
          f"model/hlo flops={row['model_flops_ratio']:.3f}  "
          f"hbm={row['hbm_gb']:.1f} GB/chip")
    an = H.HloAnalyzer(compiled.as_text(),
                       pod_size=roof.chips // (2 if multi_pod else 1)
                       if multi_pod else roof.chips)
    print("  top traffic items:")
    for sc, mult, op, iname, flash in top_items(an, top):
        tag = " [flash_xla]" if flash else ""
        print(f"    {sc/1e12:9.2f} TB x{mult:7.0f} {op:14s} {iname[:48]}{tag}")
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--shape", choices=tuple(SHAPES), required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--moment-dtype", default=None)
    ap.add_argument("--accum-dtype", default=None)
    ap.add_argument("--dispatch", default=None)
    ap.add_argument("--factored-v", action="store_true")
    ap.add_argument("--fsdp-pod", action="store_true",
                    help="extend parameter FSDP over the pod axis (ZeRO across "
                         "DCN) — the 405B-fit lever on multi-pod meshes")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--block-q", type=int, default=None)
    ap.add_argument("--block-kv", type=int, default=None)
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--label", default="")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()

    base = recipe_for(args.arch, SHAPES[args.shape])
    import dataclasses
    changes = {}
    if args.n_micro is not None:
        changes["n_micro"] = args.n_micro
    if args.moment_dtype:
        changes["moment_dtype"] = args.moment_dtype
    if args.accum_dtype:
        changes["accum_dtype"] = args.accum_dtype
    if args.dispatch:
        changes["dispatch"] = args.dispatch
    if args.factored_v:
        changes["factored_v"] = True
    if args.no_remat:
        changes["remat"] = False
    recipe = dataclasses.replace(base, **changes)
    if args.fsdp_pod:
        from repro.launch.shardings import set_fsdp_axes
        set_fsdp_axes(("pod", "data"))

    row = run(args.arch, args.shape, multi_pod=args.multi_pod, recipe=recipe,
              block_q=args.block_q, block_kv=args.block_kv, top=args.top,
              label=args.label)
    if args.json_out:
        row["label"] = args.label
        row["recipe"] = dataclasses.asdict(recipe)
        row["block_q"], row["block_kv"] = args.block_q, args.block_kv
        with open(args.json_out, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
