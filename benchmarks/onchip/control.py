"""The control of a cell's ``correct``: the program itself with its own
lower-precision path switched on — the cell's job run with every matrix
product and convolution at ``"high"`` (three bfloat16 passes), the next
precision below the ``"highest"`` float32 the configurations state —
checked against the float32 reference exactly as a benchmark run is.
Its numbers set the upper reading of each limit in
``limits/<cell>.json``; a sound limit fails it on every seed.

    python3 benchmarks/onchip/control.py --workload <name> --seeds 1,2,3

Prints one line per seed with the numbers and whether the cell's limits
catch it, and exits non-zero if any seed passes them.  Run it on the
chip, as the only process there.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from fedbench import compare, harness, spec  # noqa: E402

LOWER = {"highest": "high"}
WINDOW_S = 1.0


def control_run(cell, seed: int, devices):
    """The cell's run at the next lower precision, with a short window."""
    cell.config["matmul_precision"] = LOWER[cell.config["matmul_precision"]]
    return harness.run_cell(cell, seed, WINDOW_S, False, devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.ROOT / "src"))
    bench = spec.load_benchmark()
    devices = harness.chip_devices(spec.resolve(bench, args.workload).chips)
    harness.enable_cache()
    caught = True
    for seed in (int(x) for x in args.seeds.split(",")):
        res = control_run(spec.resolve(bench, args.workload), seed, devices)
        fails = not compare.all_within(res["compared"])
        caught &= fails
        print(json.dumps({"seed": seed, "compared": res["compared"],
                          "caught": fails}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
