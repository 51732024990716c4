import os
os.environ["XLA_FLAGS"] = (os.environ.get("PRE_XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + os.environ.get("DRYRUN_DEVICES", "512")).strip()
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as a script/module (the XLA flag above executes before any jax
import — jax locks the device count at first backend init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --both --out results/dryrun

Per cell it records: compile ok, per-device memory_analysis, cost_analysis
FLOPs/bytes, and collective-traffic bytes parsed from the post-SPMD HLO —
everything EXPERIMENTS.md §Dry-run/§Roofline consumes.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.distributed import hlo  # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402
from repro.runtime import steps as steps_mod  # noqa: E402


def input_specs(arch: str, shape: str, mesh):
    """ShapeDtypeStruct stand-ins (sharding-annotated) for every model input."""
    return steps_mod.make_step_for_cell(arch, shape, mesh).abstract_args


def _mesh(multi_pod: bool):
    """Production mesh, or a scaled trial mesh via DRYRUN_MESH=4x4 etc."""
    override = os.environ.get("DRYRUN_MESH")
    if override:
        dims = tuple(int(x) for x in override.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        return make_mesh(dims, axes)
    return make_production_mesh(multi_pod=multi_pod)


def run_cell(arch: str, shape: str, *, multi_pod: bool, verbose: bool = True):
    mesh = _mesh(multi_pod)
    rec = {"arch": arch, "shape": shape,
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "devices": mesh.devices.size}
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            bundle = steps_mod.make_step_for_cell(arch, shape, mesh)
            lowered = bundle.fn.lower(*bundle.abstract_args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = hlo.memory_analysis_dict(compiled)
            cost = hlo.flops_and_bytes(compiled)
            text = compiled.as_text()
            coll = hlo.collective_bytes(text)
            counts = hlo.collective_count(text)
            cost.update(hlo.weighted_cost(text))
            cost["attn_core_bytes"] = hlo.scoped_bytes(text, "attn_core")
            cost["score_like_bytes"] = hlo.score_like_bytes(text)
            cost["nested_scan_bytes"] = hlo.nested_scan_bytes(text)
        rec.update(ok=True, kind=bundle.kind, lower_s=round(t_lower, 1),
                   compile_s=round(t_compile, 1), memory=mem, cost=cost,
                   collective_bytes=coll, collective_counts=counts)
        if verbose:
            hbm_gb = mem["total_hbm_bytes"] / 2**30
            print(f"[OK] {arch:24s} {shape:12s} {rec['mesh']:8s} "
                  f"kind={bundle.kind:7s} hbm/dev={hbm_gb:7.2f}GiB "
                  f"flops/dev={cost['flops']:.3e} coll={coll.get('total',0):.3e}B "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)",
                  flush=True)
    except Exception as e:  # noqa: BLE001 — record and continue
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[FAIL] {arch} {shape} {rec['mesh']}: {rec['error']}",
                  flush=True)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true",
                    help="run each cell on single-pod AND multi-pod meshes")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()

    cells = registry.all_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both else [args.multi_pod]

    results = []
    for arch, shape in cells:
        for mp in meshes:
            results.append(run_cell(arch, shape, multi_pod=mp))

    if args.out:
        path = pathlib.Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(results, indent=1))
        print(f"wrote {path}")
    n_fail = sum(not r["ok"] for r in results)
    print(f"{len(results) - n_fail}/{len(results)} cells compiled")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
