import os
# a CPU compile tool: 512 host devices stand in for the production mesh, and
# pinning the platform keeps it (and the per-cell children, which inherit
# the environment) off any attached accelerator
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This proves the distribution config is coherent without hardware: GSPMD
partitioning must succeed, the per-device memory analysis must fit, and
the compiled HLO feeds the roofline analysis.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every cell, subprocess each
  python -m repro.launch.dryrun --all --mesh multi

Outputs one JSON per cell under benchmarks/results/dryrun/.
"""
import argparse          # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs import ASSIGNED                          # noqa: E402
from repro.core.config import SHAPES, TPU_V5E               # noqa: E402
from repro.core.registry import get                         # noqa: E402
from repro.core.roofline import model_flops                 # noqa: E402
from repro.core.workload import applicable                  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_name  # noqa: E402
from repro.launch.steps import build_cell, lower_cell       # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")

# Per-arch training-memory knobs (derived from the dry-run's own memory
# analysis: residual-stream scan carries ∝ L×B×S×D must fit alongside the
# optimizer).  MoE giants additionally keep Adam moments in bf16.
TRAIN_MICROBATCHES = {
    "qwen3-moe-235b-a22b": 16,
    "llama4-maverick-400b-a17b": 16,
    "glm4-9b": 8,
    "llama3-8b": 8,
    "llava-next-mistral-7b": 8,
    "mamba2-2.7b": 8,
    "zamba2-2.7b": 8,
}
BF16_OPT_STATE = {"qwen3-moe-235b-a22b", "llama4-maverick-400b-a17b"}


def run_cell(arch: str, shape: str, mesh_kind: str, out_dir: str,
             microbatches: int = 1, sequence_parallel: bool = False) -> dict:
    cfg = get(arch)
    wl = SHAPES[shape]
    ok, why = applicable(cfg, wl)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "applicable": ok, "skip_reason": why}
    if not ok:
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec["mesh_shape"] = mesh_name(mesh)
    rec["chips"] = mesh.devices.size
    t0 = time.time()
    from repro.train.optimizer import OptConfig
    mb = TRAIN_MICROBATCHES.get(arch, microbatches)
    opt = OptConfig(state_dtype="bfloat16" if arch in BF16_OPT_STATE
                    else "float32")
    rec["train_knobs"] = {"microbatches": mb, "opt_state_dtype": opt.state_dtype,
                          "sequence_parallel": sequence_parallel}
    cell = build_cell(cfg, wl, mesh, opt=opt, microbatches=mb,
                      sequence_parallel=sequence_parallel)
    rec["plan"] = {"attn_mode": cell.plan.attn_mode,
                   "kv_repeat": cell.plan.kv_repeat,
                   "moe_groups": cell.plan.moe_groups,
                   "notes": list(cell.plan.notes)}
    lowered = lower_cell(cell)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_gb": ma.argument_size_in_bytes / 1e9,
        "output_gb": ma.output_size_in_bytes / 1e9,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "code_gb": ma.generated_code_size_in_bytes / 1e9,
        "alias_gb": ma.alias_size_in_bytes / 1e9,
        "hbm_gb": TPU_V5E.hbm_bytes / 1e9,
    }
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    rec["memory"]["live_gb"] = live / 1e9
    rec["memory"]["fits"] = bool(live <= TPU_V5E.hbm_bytes)

    xca = compiled.cost_analysis()
    rec["xla_cost"] = {"flops": xca.get("flops", 0.0),
                       "bytes": xca.get("bytes accessed", 0.0)}

    t0 = time.time()
    txt = compiled.as_text()
    import gzip
    with gzip.open(os.path.join(
            out_dir, f"{arch}__{shape}__{mesh_kind}.hlo.gz"), "wt") as f:
        f.write(txt)
    from repro.core.hlo_analysis import HloAnalyzer
    an = HloAnalyzer(txt)
    cost = an.summarize()
    fused = an.summarize_fused()
    rec["analyze_s"] = round(time.time() - t0, 2)
    rec["hlo"] = {
        "flops": cost.flops, "bytes": cost.bytes,
        "coll_bytes": cost.coll_bytes,
        "by_class": cost.by_class(),
        "by_scope": cost.by_scope(),
        "n_kernels": len(cost.kernels),
    }
    # the deployed-kernel (Pallas fused attn/ssd/conv/norm) memory model
    rec["hlo_fused"] = {
        "flops": fused.flops, "bytes": fused.bytes,
        "coll_bytes": fused.coll_bytes,
        "by_class": fused.by_class(),
    }
    rec["model_flops"] = model_flops(cfg, wl)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--timeout", type=int, default=1200)
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel residual stream (beyond-paper)")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        archs = [args.arch] if args.arch else list(ASSIGNED)
        shapes = [args.shape] if args.shape else list(SHAPES)
        failures = []
        for arch in archs:
            for shape in shapes:
                for mk in meshes:
                    tag = f"{arch}__{shape}__{mk}"
                    path = os.path.join(args.out, tag + ".json")
                    if os.path.exists(path):
                        print(f"[skip existing] {tag}")
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape, "--mesh", mk,
                           "--out", args.out,
                           "--microbatches", str(args.microbatches)] \
                        + (["--sp"] if args.sp else [])
                    print(f"[run] {tag}", flush=True)
                    r = subprocess.run(cmd, timeout=args.timeout)
                    if r.returncode != 0:
                        failures.append(tag)
        print("FAILURES:", failures if failures else "none")
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape
    tag = f"{args.arch}__{args.shape}__{args.mesh}"
    try:
        rec = run_cell(args.arch, args.shape, args.mesh, args.out,
                       microbatches=args.microbatches,
                       sequence_parallel=args.sp)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "applicable": True, "error": traceback.format_exc()}
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
        print(rec["error"])
        sys.exit(1)
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    if rec.get("applicable"):
        m = rec["memory"]
        print(f"[ok] {tag}: compile={rec['compile_s']}s "
              f"live={m['live_gb']:.2f}GB fits={m['fits']} "
              f"flops/dev={rec['hlo']['flops']:.3e} "
              f"coll={rec['hlo']['coll_bytes']:.3e}B")
    else:
        print(f"[n/a] {tag}: {rec['skip_reason']}")


if __name__ == "__main__":
    main()
