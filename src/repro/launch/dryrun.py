import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any jax import: jax locks the device count at
first init, and the production meshes need 512 host placeholder devices.

Per cell this runs::

    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=..., out_shardings=...).lower(**specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for the roofline

and records the roofline terms (repro.launch.roofline) to a JSON file.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun                    # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --multi-pod        # 2x16x16 mesh
    PYTHONPATH=src python -m repro.launch.dryrun --out runs/dryrun.json
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, shape_applicable
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch.roofline import analyze
from repro.launch.steps import build_cell


def run_cell(arch: str, shape_name: str, mesh, *, verbose: bool = True) -> dict:
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh)
    with jax.set_mesh(mesh):
        lowered = cell.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = None
        try:
            ma = compiled.memory_analysis()
            mem = {
                "argument_gb": ma.argument_size_in_bytes / 1e9,
                "output_gb": ma.output_size_in_bytes / 1e9,
                "temp_gb": ma.temp_size_in_bytes / 1e9,
                "alias_gb": ma.alias_size_in_bytes / 1e9,
                "total_gb": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                             + ma.output_size_in_bytes
                             - ma.alias_size_in_bytes) / 1e9,
            }
            if verbose:
                print(f"    memory_analysis: {mem}")
        except Exception as e:                            # pragma: no cover
            print(f"    memory_analysis unavailable: {e}")
        roof = analyze(compiled, arch=arch, shape=SHAPES[shape_name], mesh=mesh,
                       cfg=cell.cfg,
                       device_kind=PRODUCTION_DEVICE_KIND)
        row = roof.row()
        row.update({"status": "ok", "lower_s": round(t_lower, 1),
                    "compile_s": round(t_compile, 1), "memory": mem})
        if verbose:
            ca = compiled.cost_analysis()
            print(f"    cost_analysis: flops/chip={ca.get('flops', 0):.3e} "
                  f"bytes/chip={ca.get('bytes accessed', 0):.3e}")
            print(f"    roofline: compute={roof.compute_s*1e3:.2f}ms "
                  f"memory={roof.memory_s*1e3:.2f}ms "
                  f"collective={roof.collective_s*1e3:.2f}ms "
                  f"dominant={roof.dominant} mfu={roof.mfu:.3f}")
        return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCHS, default=None)
    ap.add_argument("--shape", choices=tuple(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 (pod,data,model) mesh")
    ap.add_argument("--both-meshes", action="store_true",
                    help="run every cell on single-pod AND multi-pod meshes")
    ap.add_argument("--out", default=None, help="append JSON lines here")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results, failures = [], []
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "x".join(str(v) for v in mesh.shape.values())
        for arch in archs:
            for shape_name in shapes:
                if not shape_applicable(arch, shape_name):
                    print(f"[skip] {arch} x {shape_name} (full attention at "
                          "500k; see DESIGN.md §Arch-applicability)")
                    results.append({"arch": arch, "shape": shape_name,
                                    "mesh": mesh_name, "status": "skip"})
                    continue
                print(f"[cell] {arch} x {shape_name} on {mesh_name} ...",
                      flush=True)
                try:
                    row = run_cell(arch, shape_name, mesh)
                    results.append(row)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape_name, mesh_name, str(e)[:200]))
                    results.append({"arch": arch, "shape": shape_name,
                                    "mesh": mesh_name, "status": "fail",
                                    "error": str(e)[:500]})
                if args.out:
                    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                    with open(args.out, "w") as f:
                        for r in results:
                            f.write(json.dumps(r) + "\n")

    ok = sum(1 for r in results if r.get("status") == "ok")
    skip = sum(1 for r in results if r.get("status") == "skip")
    print(f"\n=== dry-run: {ok} ok, {skip} skipped, {len(failures)} failed ===")
    for f in failures:
        print("  FAIL:", f)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
