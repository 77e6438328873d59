import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""§Perf hillclimb driver: lower+compile named variants of the three chosen
(arch × shape) pairs and record the roofline deltas.

Each variant is a (description, build-kwargs) pair; results append to
``benchmarks/artifacts/hillclimb.json`` with hypothesis / before / after
for EXPERIMENTS.md §Perf.

    PYTHONPATH=src python -m repro.launch.hillclimb --pair deepseek
"""
import argparse
import json
from pathlib import Path

from repro.launch.dryrun import run_one

ART = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts"

# variant grids per hillclimb pair; "hypothesis" is written before measuring
PAIRS = {
    "deepseek": {
        "arch": "deepseek_v2_236b", "shape": "train_4k",
        "variants": [
            ("baseline", "paper-faithful FedAvg round (local_steps=1, micro=4, "
             "no activation-sharding hints) — the reproduction reference",
             {"kw": {"hints": False}}),
            ("qkv_hints", "H: 32 TB/round of f32 score all-reduces (measured "
             "via HLO triage: [mb,8h,512,4096] x30208) come from the MLA "
             "nope/rope concat losing head sharding; constraining q/k/v to "
             "head-sharded makes score contractions device-local -> "
             "collective ~6x down", {}),
            ("micro8", "H(prior iteration, refuted): collective was per-"
             "microbatch grad syncs; micro 4->8 should halve it. Re-test on "
             "top of hints.", {"kw": {"microbatch": 8}}),
            ("local4", "H: with collectives fixed, FedAvg full-param exchange "
             "amortizes over local_steps=4; per-STEP terms (divide by 4) "
             "should drop only in the exchange share",
             {"kw": {"local_steps": 4}}),
            ("gather_moe", "H(refuted decisively in iteration 1): token-"
             "gather MoE gathers [T,k,D,F] weight copies -> 2 TiB/device. "
             "Not re-run; recorded for the log.", None),
        ],
    },
    "rwkv6": {
        "arch": "rwkv6_7b", "shape": "train_4k",
        "variants": [
            ("baseline", "paper-faithful FedAvg round, 16 sites, TP=16, "
             "micro=8, no hints", {"kw": {"hints": False}}),
            ("qkv_hints", "H: same score-resharding class as deepseek does "
             "not apply (attention-free) -> expect no change from hints",
             {}),
            ("micro16", "H: grad reductions per microbatch dominate "
             "collectives; micro 8->16 (single sync per site step)",
             {"kw": {"microbatch": 16}}),
            ("fsdp2", "H(refuted): sites=8 x fsdp=2 halves sites but "
             "doubles per-site tokens -> per-device collective GREW 2x "
             "(26->54 s). Lesson: collective here scales with tokens/device, "
             "not site count.", None),
            ("tp4", "H(from HLO triage: [mb,4096,14336] activation "
             "all-reduce/gathers x64 = row-parallel TP traffic): TP=16 is "
             "overkill for 7.6B; refactor the FL view to (site=16, fsdp=4, "
             "model=4) -> per-device activation shards (batch/4) and psum "
             "group (4 vs 16) both shrink -> collective ~3-4x down",
             {"mesh": {"sites_per_pod": 16, "fsdp": 4, "model_parallel": 4}}),
        ],
    },
    "qwen3": {
        "arch": "qwen3_8b", "shape": "train_4k",
        "variants": [
            ("baseline", "paper-faithful FedAvg round, 16 sites, micro=4, "
             "no hints", {"kw": {"hints": False}}),
            ("qkv_hints", "H: qwen3 GQA (32q/8kv heads, head concat-free) "
             "already head-shards cleanly; hints should be ~neutral", {}),
            ("micro8", "H: memory term ~ params re-read per microbatch "
             "(8.2B bf16 x fwd+bwd x n_micro); micro 4->8 cuts param "
             "traffic share ~2x", {"kw": {"microbatch": 8}}),
            ("micro16", "H: continues 8->16 until activation carries "
             "dominate", {"kw": {"microbatch": 16}}),
        ],
    },
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", choices=list(PAIRS) + ["all"], default="all")
    args = ap.parse_args()
    pairs = list(PAIRS) if args.pair == "all" else [args.pair]
    log_path = ART / "hillclimb.json"
    log = json.loads(log_path.read_text()) if log_path.exists() else {}
    for pname in pairs:
        spec = PAIRS[pname]
        entries = log.setdefault(pname, [])
        for vname, hypothesis, opts in spec["variants"]:
            if opts is None:
                entries.append({"variant": vname, "hypothesis": hypothesis,
                                "skipped": "recorded from iteration 1"})
                continue
            kw = dict(opts.get("kw", {}))
            if "mesh" in opts:
                from repro.configs.base import MeshConfig
                kw["override_mesh"] = MeshConfig(**opts["mesh"])
            print(f"\n=== {pname}:{vname} ===\n  {hypothesis}")
            rec = run_one(spec["arch"], spec["shape"], multi_pod=False,
                          save=False, **kw)
            entries.append({
                "variant": vname, "hypothesis": hypothesis,
                "roofline": rec["roofline"],
                "collectives": rec["collective_bytes"],
                "memory_gib": (rec["memory"]["argument_bytes"]
                               + rec["memory"]["temp_bytes"]
                               + rec["memory"]["output_bytes"]) / 2 ** 30,
                "flops": rec["flops"], "bytes": rec["bytes_accessed"],
            })
            log_path.write_text(json.dumps(log, indent=2))
    print("\nhillclimb log written to", log_path)


if __name__ == "__main__":
    main()
