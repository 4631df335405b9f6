import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", ""))
# ^ MUST run before any other import (jax locks device count on first init).

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape) cell, on the single-pod (16,16) and
multi-pod (2,16,16) meshes: ``jax.jit(step).lower(*input_specs).compile()``,
then record

  * ``compiled.memory_analysis()``  (per-chip bytes — proves it fits)
  * ``compiled.cost_analysis()``    (XLA's own numbers, while-body-once)
  * trip-count-corrected FLOPs / bytes / collective wire bytes from our HLO
    parser (repro.utils.hlo) — the numbers §Roofline uses

into results/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
"""

import argparse
import json
import time
import traceback

import jax
import numpy as np


def run_cell(arch: str, shape_id: str, mesh_kind: str, out_dir: str,
             overrides=None, tag: str = "") -> dict:
    from repro.configs import get_config, shape_applicable, SHAPES
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_cell
    from repro.utils.hlo import analyze_hlo_text, cost_summary

    cfg = get_config(arch)
    rec = {
        "arch": arch, "shape": shape_id, "mesh": mesh_kind, "tag": tag,
        "status": "ok", "time_s": None,
    }
    if not shape_applicable(cfg, shape_id):
        rec["status"] = "skipped_by_design"
        rec["reason"] = ("long_500k requires sub-quadratic decode context; "
                        f"{arch} is pure full attention (DESIGN.md §4)")
        return _write(rec, out_dir)

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    try:
        cell = build_cell(cfg, shape_id, mesh, overrides=dict(overrides or {}))
        with jax.set_mesh(mesh):
            jitted = jax.jit(cell.step_fn, donate_argnums=cell.donate)
            lowered = jitted.lower(*cell.args)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: getattr(mem, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes")
            if hasattr(mem, k)}
        ca = compiled.cost_analysis()
        rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float))
                                and k in ("flops", "bytes accessed",
                                          "transcendentals", "utilization")}
        hlo_text = compiled.as_text()
        cost = analyze_hlo_text(hlo_text)
        rec["hlo_cost"] = cost_summary(cost)
        rec["hlo_bytes"] = len(hlo_text)
        # cache compressed HLO so the cost model can be refined without
        # recompiling (scripts/reanalyze.py)
        try:
            import zstandard as zstd
            tagp = f"__{tag}" if tag else ""
            os.makedirs(out_dir, exist_ok=True)
            hpath = os.path.join(
                out_dir, f"{arch}__{shape_id}__{mesh_kind}{tagp}.hlo.zst")
            with open(hpath, "wb") as f:
                f.write(zstd.ZstdCompressor(level=6).compress(
                    hlo_text.encode()))
        except Exception:
            pass
        rec["tokens_per_step"] = cell.tokens_per_step
        rec["kind"] = cell.kind
        rec["model_params"] = cfg.param_count()
        rec["active_params"] = cfg.active_param_count()
        rec["model_flops_total"] = cfg.model_flops(
            cell.tokens_per_step, training=(cell.kind == "train"))
        rec["num_devices"] = mesh.size
        rec["time_s"] = round(time.time() - t0, 1)
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        rec["time_s"] = round(time.time() - t0, 1)
    return _write(rec, out_dir)


def _write(rec: dict, out_dir: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"__{rec['tag']}" if rec.get("tag") else ""
    path = os.path.join(
        out_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec["status"]
    extra = ""
    if status == "ok":
        ma = rec.get("memory_analysis", {})
        extra = (f" args={ma.get('argument_size_in_bytes', 0)/2**30:.2f}GiB"
                 f" temp={ma.get('temp_size_in_bytes', 0)/2**30:.2f}GiB"
                 f" flops/dev={rec['hlo_cost']['flops']:.3g}"
                 f" wire={rec['hlo_cost']['collective_wire_bytes']:.3g}B"
                 f" t={rec['time_s']}s")
    elif status == "error":
        extra = " " + rec["error"][:160]
    print(f"[dryrun] {rec['arch']:24s} {rec['shape']:12s} {rec['mesh']:6s} "
          f"{status}{extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    from repro.configs import ARCH_IDS, SHAPE_IDS

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = SHAPE_IDS if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"__{args.tag}" if args.tag else ""
                path = os.path.join(
                    args.out, f"{arch}__{shape}__{mesh_kind}{tag}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("status") in ("ok", "skipped_by_design"):
                            print(f"[dryrun] skip existing {path}", flush=True)
                            continue
                run_cell(arch, shape, mesh_kind, args.out, tag=args.tag)


if __name__ == "__main__":
    main()
