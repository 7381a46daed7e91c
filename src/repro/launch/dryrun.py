import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and record memory / cost / collective
analysis for the roofline (EXPERIMENTS.md §Dry-run, §Roofline).

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k \
      --mesh single|multi [--sync hierarchical|flat] [--out runs/dryrun]
  python -m repro.launch.dryrun --all [--mesh both]
"""
import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402

from .. import configs   # noqa: E402
from ..core import collectives  # noqa: E402
from ..models.config import SHAPES_BY_NAME, applicable_shapes, skip_reason  # noqa: E402
from . import hlo_analysis, roofline, steps  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

HBM_PER_CHIP = 16 * 1024 ** 3   # v5e


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             sync_mode: str = "hierarchical", out_dir: str = "runs/dryrun",
             save_hlo: bool = False) -> dict:
    cfg = configs.get(arch)
    shape = SHAPES_BY_NAME[shape_name]
    reason = skip_reason(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "sync": sync_mode, "kind": shape.kind}
    if reason:
        rec["status"] = "skip"
        rec["skip_reason"] = reason
        return rec

    sync = (collectives.FLAT if sync_mode == "flat"
            else collectives.HIERARCHICAL)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    if shape.kind == "train":
        fn, _ = steps.build_train_step(cfg, mesh, sync=sync)
        args = steps.input_specs(cfg, shape, mesh, sync=sync)
    elif shape.kind == "prefill":
        fn, _ = steps.build_prefill_step(cfg, mesh,
                                         batch=shape.global_batch,
                                         seq_len=shape.seq_len)
        args = steps.input_specs(cfg, shape, mesh)
    else:
        fn, _ = steps.build_decode_step(cfg, mesh,
                                        batch=shape.global_batch,
                                        max_len=shape.seq_len)
        args = steps.input_specs(cfg, shape, mesh)

    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
    rec["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
    }
    resident = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    rec["memory"]["resident_bytes"] = int(resident)
    rec["memory"]["fits_16gib"] = bool(resident <= HBM_PER_CHIP)

    cost = compiled.cost_analysis() or {}
    rec["xla_cost"] = {k: float(v) for k, v in cost.items()
                      if k in ("flops", "bytes accessed")}

    hlo = compiled.as_text()
    rec["hlo_chars"] = len(hlo)
    stats = hlo_analysis.analyze(hlo)
    rec["hlo"] = {
        "flops": stats.flops,
        "hbm_bytes": stats.hbm_bytes,
        "collective_bytes": stats.collective_bytes,
        "collective_by_type": stats.collective_by_type,
        "collective_count": stats.collective_count,
    }
    rl = roofline.compute_roofline(
        cfg, shape, n_chips=n_chips, hlo_flops=stats.flops,
        hbm_bytes=stats.hbm_bytes, wire_bytes=stats.collective_bytes)
    rec["roofline"] = rl.as_dict()
    rec["status"] = "ok"

    if save_hlo:
        Path(out_dir).mkdir(parents=True, exist_ok=True)
        hpath = Path(out_dir) / f"{arch}_{shape_name}_{mesh_name}.hlo"
        hpath.write_text(hlo)
        rec["hlo_path"] = str(hpath)
    return rec


def save(rec: dict, out_dir: str):
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    name = (f"{rec['arch']}_{rec['shape']}_{rec['mesh']}"
            f"_{rec.get('sync', 'hierarchical')}.json")
    (out / name).write_text(json.dumps(rec, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--sync", default="hierarchical",
                    choices=["hierarchical", "flat"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    meshes = (["single", "multi"] if args.mesh == "both"
              else [args.mesh])
    cells = []
    if args.all:
        for arch in configs.ARCH_IDS:
            cfg = configs.get(arch)
            for shape in SHAPES_BY_NAME.values():
                cells.append((arch, shape.name))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(configs.ALIASES.get(args.arch, args.arch), args.shape)]

    failures = 0
    for arch, shape in cells:
        for mesh_name in meshes:
            key = f"{arch} x {shape} x {mesh_name}"
            try:
                rec = run_cell(arch, shape, mesh_name == "multi",
                               args.sync, args.out,
                               save_hlo=args.save_hlo)
            except Exception as e:  # noqa: BLE001
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "sync": args.sync, "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                failures += 1
            save(rec, args.out)
            status = rec["status"]
            extra = ""
            if status == "ok":
                r = rec["roofline"]
                extra = (f" bottleneck={r['bottleneck']}"
                         f" mfu={r['mfu']:.3f}"
                         f" resident={rec['memory']['resident_bytes']/2**30:.1f}GiB"
                         f" fits={rec['memory']['fits_16gib']}"
                         f" compile={rec['compile_s']}s")
            elif status == "skip":
                extra = f" ({rec['skip_reason']})"
            else:
                extra = f" {rec['error'][:120]}"
            print(f"[dryrun] {key}: {status}{extra}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
