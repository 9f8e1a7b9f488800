import os
from repro.launch.hostdev import force_host_devices
force_host_devices(512)   # before any jax import — see module docstring

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, prove it partitions, and extract the roofline terms.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # 40 pairs
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Each run writes experiments/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis, cost_analysis, collective breakdown and roofline terms.
NOTE: the force_host_devices call above must execute before any other jax
import — do not move it (and never set it globally; smoke tests want 1
device).  It APPENDS to a pre-existing XLA_FLAGS rather than clobbering it.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback

import jax

from repro.configs import (ARCH_IDS, INPUT_SHAPES, get_config, get_shape,
                           supported_shapes)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import lower_step
from repro.models.api import build_model
from repro.roofline.analysis import model_flops_estimate, roofline_terms
from repro.sharding.rules import Rules, use_rules

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def variant_for(cfg, shape):
    """long_500k on quadratic-attention families runs the sliding-window
    variant (DESIGN.md §4)."""
    if shape.name == "long_500k" and cfg.family in ("dense", "moe", "vlm"):
        return cfg.replace(attention="sliding_window")
    return cfg


# §Perf hillclimb variants (EXPERIMENTS.md §Perf): comma-separable.
#   bf16     — bf16 parameters (halves param/grad/collective bytes)
#   tponly   — drop FSDP sharding (no per-layer param all-gathers)
#   seqscan  — SSM: sequential scan, kernel-equivalent data movement
#   nomoeaux — (reserved)
def apply_variants(cfg, variant: str):
    rules_table = {}
    for v in filter(None, (variant or "").split(",")):
        if v == "baseline":
            continue
        elif v == "bf16":
            cfg = cfg.replace(param_dtype="bfloat16")
        elif v == "tponly":
            rules_table["fsdp"] = ()
        elif v == "decode2d":
            # serving layout: weights 2D-sharded on their OUTPUT dims
            # (model x data) so matmuls are collective-free or end in tiny
            # activation all-reduces; decode activation batch replicated;
            # no contraction-dim (fsdp) weight sharding -> no weight
            # all-gathers.  KV/state caches stay batch-sharded over data.
            rules_table.update({
                "batch": (), "fsdp": (),
                "ff": ("model", "data"),
                "ssm_inner": ("model", "data"),
                "heads": ("model", "data"),
                "vocab": ("model", "data"),
                "expert_ff": ("data",),
            })
        elif v == "seqscan":
            cfg = cfg.replace(ssm_scan="sequential")
        elif v == "ssmbf16":
            cfg = cfg.replace(ssm_input_dtype="bfloat16")
        elif v.startswith("chunk"):
            cfg = cfg.replace(ssm_chunk=int(v[5:]))
        else:
            raise ValueError(f"unknown variant {v!r}")
    return cfg, Rules(table=rules_table)


def run_one(arch: str, shape_id: str, multi_pod: bool = False,
            optimizer: str = "sgd", out_dir: str = OUT_DIR,
            variant: str = "baseline") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_devices = mesh.devices.size
    shape = get_shape(shape_id)
    cfg = variant_for(get_config(arch), shape)
    cfg, rules = apply_variants(cfg, variant)
    model = build_model(cfg)

    t0 = time.time()
    with jax.set_mesh(mesh), use_rules(rules):
        lowered, kind = lower_step(model, shape, mesh, optimizer)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        try:
            ma = compiled.memory_analysis()
            mem = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            }
        except Exception as e:  # pragma: no cover
            mem = {"error": str(e)}
        try:
            ca = compiled.cost_analysis() or {}
            cost = {k: float(v) for k, v in ca.items()
                    if k in ("flops", "bytes accessed")}
        except Exception as e:  # pragma: no cover
            cost = {"error": str(e)}
        text = compiled.as_text()

    bytes_per_device = (mem.get("argument_bytes", 0)
                        + mem.get("temp_bytes", 0))
    report = roofline_terms(
        text, n_devices, arch=arch, shape=shape_id, mesh=mesh_name,
        model_flops=model_flops_estimate(cfg, shape),
        bytes_per_device=bytes_per_device)

    result = {
        "arch": arch, "shape": shape_id, "mesh": mesh_name, "kind": kind,
        "optimizer": optimizer if kind == "train" else None,
        "variant": variant,
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem, "xla_cost_analysis": cost,
        "hlo": {
            "flops": report.flops,
            "bytes_accessed": report.bytes_accessed,
            "collective_bytes": report.collective_bytes,
            "collective_breakdown": report.collective_breakdown,
        },
        "roofline": {
            "t_compute_ms": report.t_compute * 1e3,
            "t_memory_ms": report.t_memory * 1e3,
            "t_collective_ms": report.t_collective * 1e3,
            "bottleneck": report.bottleneck,
            "model_flops": report.model_flops,
            "useful_flops_ratio": report.useful_ratio,
            "bytes_per_device_gib": bytes_per_device / 2 ** 30,
        },
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = "" if variant == "baseline" else f"__{variant.replace(',', '+')}"
    fname = f"{arch}__{shape_id}__{mesh_name}{suffix}.json"
    if os.environ.get("DRYRUN_DUMP_HLO"):
        with open(os.path.join(out_dir, fname.replace(".json", ".hlo.txt")),
                  "w") as f:
            f.write(text)
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=2)
    return result


def _pairs():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape_id in supported_shapes(cfg):
            yield arch, shape_id


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--optimizer", default="sgd", choices=("sgd", "adamw"))
    ap.add_argument("--variant", default="baseline",
                    help="comma-separated: bf16,tponly,seqscan")
    ap.add_argument("--subprocess", action="store_true",
                    help="run each pair in a fresh process (isolates OOM)")
    ap.add_argument("--out-dir", default=OUT_DIR)
    args = ap.parse_args()

    if args.all:
        failures = []
        for arch, shape_id in _pairs():
            if args.subprocess:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_id,
                       "--optimizer", args.optimizer,
                       "--variant", args.variant,
                       "--out-dir", args.out_dir]
                if args.multi_pod:
                    cmd.append("--multi-pod")
                rc = subprocess.run(cmd).returncode
                status = "ok" if rc == 0 else f"FAIL rc={rc}"
                if rc != 0:
                    failures.append((arch, shape_id))
                print(f"[dryrun] {arch} x {shape_id}: {status}", flush=True)
            else:
                try:
                    r = run_one(arch, shape_id, args.multi_pod, args.optimizer,
                                args.out_dir, args.variant)
                    rf = r["roofline"]
                    print(f"[dryrun] {arch} x {shape_id} ({r['mesh']}): ok "
                          f"compute={rf['t_compute_ms']:.2f}ms "
                          f"mem={rf['t_memory_ms']:.2f}ms "
                          f"coll={rf['t_collective_ms']:.2f}ms "
                          f"-> {rf['bottleneck']}", flush=True)
                except Exception:
                    failures.append((arch, shape_id))
                    print(f"[dryrun] {arch} x {shape_id}: FAIL\n"
                          f"{traceback.format_exc()}", flush=True)
        if failures:
            print(f"FAILURES: {failures}")
            sys.exit(1)
        print("dry-run: all pairs lowered + compiled OK")
        return

    r = run_one(args.arch, args.shape, args.multi_pod, args.optimizer,
                args.out_dir, args.variant)
    print(json.dumps(r, indent=2))


if __name__ == "__main__":
    main()
