import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combo.

The lines above MUST stay first (they add to the caller's XLA_FLAGS,
never replace them): jax locks the device count on first
initialization, and the production meshes need 512 placeholder devices.

For each combination this script:
  1. builds the step (federated train round / serve prefill / serve decode)
  2. ``jax.jit(...).lower(*abstract)`` on the single-pod 16x16 mesh AND the
     2x16x16 multi-pod mesh
  3. ``.compile()`` — sharding mismatches / OOM / unsupported collectives
     fail HERE, which is the point
  4. records memory_analysis(), cost_analysis() and the collective-bytes
     breakdown parsed from the compiled HLO into a JSON artifact consumed
     by EXPERIMENTS.md §Dry-run / §Roofline and benchmarks/roofline.py

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ALIASES, ARCH_IDS, is_skipped
from repro.launch.hlo_analysis import analyze
from repro.launch.roofline import roofline_report
from repro.launch.steps import build

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts"


def run_one(arch_id: str, shape_name: str, multi_pod: bool,
            save: bool = True, verbose: bool = True, **kw) -> dict:
    t0 = time.time()
    art = build(arch_id, shape_name, multi_pod=multi_pod, **kw)
    with art.mesh:
        jitted = jax.jit(art.step_fn, in_shardings=art.in_shardings,
                         out_shardings=art.out_shardings)
        lowered = jitted.lower(*art.abstract_inputs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = analyze(compiled.as_text())
    n_dev = art.mesh.devices.size
    rec = {
        "name": art.name,
        "arch": arch_id,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "devices": int(n_dev),
        "notes": art.notes,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # while-aware HLO analysis (trip-count-corrected; see hlo_analysis.py)
        "flops": hlo.flops,
        "bytes_accessed": hlo.bytes,
        "collective_bytes": {**hlo.collective_bytes, "count": hlo.collective_count},
        # XLA's own (per-body-once) numbers kept as a cross-check
        "xla_cost_flops_once": float(cost.get("flops", 0.0)),
        "xla_cost_bytes_once": float(cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
    }
    rec["roofline"] = roofline_report(rec)
    if verbose:
        print(f"[dryrun] {art.name}: lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory/device: args {mem.argument_size_in_bytes/2**30:.2f} GiB "
              f"temp {mem.temp_size_in_bytes/2**30:.2f} GiB "
              f"out {mem.output_size_in_bytes/2**30:.2f} GiB")
        print(f"  HLO flops {rec['flops']:.3e}  bytes {rec['bytes_accessed']:.3e}"
              f"  (xla-once: {rec['xla_cost_flops_once']:.2e})")
        print(f"  collectives: { {k: f'{v:.3e}' for k, v in rec['collective_bytes'].items() if v} }")
        r = rec["roofline"]
        print(f"  roofline: compute {r['compute_s']:.4f}s memory {r['memory_s']:.4f}s "
              f"collective {r['collective_s']:.4f}s -> bound: {r['bound']}")
    if save:
        ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
        fn = ARTIFACT_DIR / f"dryrun_{arch_id}_{shape_name}_{'2pod' if multi_pod else '1pod'}.json"
        fn.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id or alias (default: all)")
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES), help="default: all")
    ap.add_argument("--multi-pod", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-save", action="store_true")
    args = ap.parse_args()

    archs = ([ALIASES.get(args.arch, args.arch)] if args.arch
             else [a for a in ARCH_IDS if a != "sanet_openkbp"])
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]

    failures, skips = [], []
    for arch_id in archs:
        for shape_name in shapes:
            reason = is_skipped(arch_id, shape_name)
            if reason:
                skips.append((arch_id, shape_name, reason))
                print(f"[skip] {arch_id}:{shape_name} — {reason}")
                continue
            for mp in pods:
                try:
                    run_one(arch_id, shape_name, mp, save=not args.no_save)
                except Exception as e:  # noqa: BLE001 — report all failures at end
                    failures.append((arch_id, shape_name, mp, repr(e)))
                    traceback.print_exc()
    print(f"\n[dryrun] done. {len(failures)} failures, {len(skips)} documented skips.")
    for f in failures:
        print("  FAIL:", f)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
