"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one row per benchmark).

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer FL rounds (CI mode)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (agg_engine, comm_bytes, dose_prediction,
                            gossip_robustness, parallel_scaling, pod_scaling,
                            privacy_tradeoff, robust_agg, roofline,
                            round_engine, strategy_compare)
    benches = [
        ("dose_prediction_fig7_8_9", dose_prediction.run),
        ("strategy_compare_fig11_12", strategy_compare.run),
        ("gossip_robustness_fig15", gossip_robustness.run),
        ("comm_bytes_table1", comm_bytes.run),
        ("agg_engine_eq1", agg_engine.run),
        ("round_engine_scan", round_engine.run),
        ("pod_scaling_two_tier", pod_scaling.run),
        ("privacy_tradeoff_eps", privacy_tradeoff.run),
        ("robust_agg_byzantine", robust_agg.run),
        ("parallel_scaling_sec3a4", parallel_scaling.run),
        ("cross_device_scaling", parallel_scaling.cross_device),
        ("roofline_dryrun", roofline.run),
    ]
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            derived, _ = fn(quick=args.quick)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
            derived = f"ERROR:{e!r}"
        us = (time.time() - t0) * 1e6
        print(f"{name},{us:.0f},{derived}")
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
