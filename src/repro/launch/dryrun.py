"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be imported/run before any other jax usage: the first two lines pin
512 placeholder host devices so the production meshes can be built.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-too]
Outputs one JSON per cell under experiments/dryrun/.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402

from repro.analysis import roofline as rl              # noqa: E402
from repro.configs import (ARCHS, SHAPES, cell_runnable,  # noqa: E402
                           get_config)
from repro.launch.mesh import make_production_mesh     # noqa: E402
from repro.launch.steps import make_step               # noqa: E402
from repro.parallel.sharding import Rules              # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             rules_table=None, out_dir: str = OUT_DIR, tag: str = "",
             donate_cache: bool = False, cfg_patch=None,
             verbose: bool = True):
    cfg = get_config(arch)
    if cfg_patch:
        import dataclasses
        cfg = dataclasses.replace(cfg, **cfg_patch)
    shape = SHAPES[shape_name]
    ok, why = cell_runnable(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    if not ok:
        return {"cell": cell, "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules = Rules(mesh, rules_table)
    t0 = time.time()
    rec = {"cell": cell, "arch": arch, "shape": shape_name,
           "mesh": mesh_name, "chips": chips}
    try:
        fn, args, in_sh, out_sh = make_step(cfg, shape, rules)
        donate = ()
        if donate_cache and shape.kind == "decode":
            donate = (1,)            # alias the KV/state cache in->out
        with jax.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*jax.tree.map(lambda x: x, args))
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
            r = rl.analyze(compiled, chips=chips,
                           model_flops=rl.model_flops_for(cfg, shape),
                           hlo_text=hlo)
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": None if mem is None else {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "total_bytes_per_device": (
                    mem.argument_size_in_bytes + mem.temp_size_in_bytes
                    + mem.output_size_in_bytes - mem.alias_size_in_bytes),
            },
            "roofline": r.to_dict(),
            "hlo_bytes": len(hlo),
        })
        if verbose:
            mm = rec["memory"]["total_bytes_per_device"] / 2**30
            print(f"[dryrun] {cell}: OK compile={t_compile:.1f}s "
                  f"mem/dev={mm:.2f}GiB bottleneck={r.bottleneck} "
                  f"t=({r.t_compute:.4f},{r.t_memory:.4f},"
                  f"{r.t_collective:.4f})s", flush=True)
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()})
        if verbose:
            print(f"[dryrun] {cell}: FAIL {type(e).__name__}: {e}",
                  flush=True)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def optimized_kwargs(shape_name: str) -> dict:
    """The tuned configuration from EXPERIMENTS.md §Perf: context/sequence
    parallelism + one-shot attention for batch steps; context-parallel
    donated caches + single-pass decode attention for decode steps."""
    if SHAPES[shape_name].kind == "decode":
        return {"rules_table": {"seq": "model"}, "donate_cache": True,
                "cfg_patch": {"decode_kv_chunk": 0}}
    return {"rules_table": {"seq": "model"},
            "cfg_patch": {"flash_chunking": False}}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCHS)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 multi-pod mesh")
    ap.add_argument("--multi-pod-too", action="store_true",
                    help="run each cell on both meshes")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the EXPERIMENTS.md §Perf tuned options")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    cells = []
    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = [False, True] if args.multi_pod_too else [args.multi_pod]
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                kw = optimized_kwargs(shape) if args.optimized else {}
                rec = run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                               tag=args.tag, **kw)
                cells.append(rec)
                n_fail += rec["status"] == "error"
    n_ok = sum(r["status"] == "ok" for r in cells)
    n_skip = sum(r["status"] == "skipped" for r in cells)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_fail} failed "
          f"of {len(cells)} cells")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
