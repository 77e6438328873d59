"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/onchip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout and live as files under
this directory (see ``fedbench/spec.py``).  The run refuses, with a
non-zero exit and no result, anywhere but a TPU with the chips the cell
asks for.  Its last line on standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), and last ``compared``: each number that
decided ``correct`` beside its limit.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from fedbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main())
