"""The per-layer metrics that read the program's own host spans
(``repro.telemetry``): after a run of a cell at a size a test run holds,
on the CPU, each reader finds its spans in the window, the input bytes
equal what the cell's shapes give, and a program that records no spans
gives no value rather than an error."""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "onchip"
sys.path.insert(0, str(BENCH))

from fedbench import harness, spec  # noqa: E402

TINY = dict(volume=[8, 8, 8], base_filters=4, num_levels=2)
NAMES = ("host_ms_per_round", "input_mb_per_round", "chunk_lower_s",
         "chunk_compile_s")


def batch_bytes(cell, sites: int) -> int:
    """One round's cases as the pool serves them: float32 input channels
    plus the float32 dose and mask, one case per site."""
    c = cell.config
    voxels = math.prod(c["volume"])
    return sites * cell.traffic["batch"] * voxels * (c["in_channels"] + 2) * 4


def readings(cell, res):
    r = harness.Reading(trace=None, cell=cell, rounds=res["attempted"],
                        window_s=0.0, chips=cell.chips, peaks=None,
                        round_flops=0, num_params=0)
    return {n: spec.metric_reader(n)(r) for n in NAMES}


@pytest.fixture(scope="module")
def dose_run():
    import jax
    cell = spec.resolve(spec.load_benchmark(), "dose-s4-v56")
    cell.config.update(TINY)
    res = harness.run_cell(cell, 2 ** 35 + 11, 0.3, False, jax.devices()[:1])
    return cell, res, readings(cell, res)


def test_every_span_metric_is_declared():
    declared = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for n in NAMES:
        assert declared[n]["moves"] in ("rounds_per_s", "setup_s")
        assert (BENCH / "metrics" / f"{n}.py").is_file()


def test_span_readers_after_a_scan_run(dose_run):
    cell, res, got = dose_run
    assert res["attempted"] >= 3
    assert all(math.isfinite(v) for v in got.values()), got
    assert got["host_ms_per_round"] >= 0.0
    assert got["chunk_lower_s"] > 0.0 and got["chunk_compile_s"] > 0.0
    s = cell.traffic["sites"]
    # masks: active (bool), partner (int32), is_receiver (bool) per site
    want = batch_bytes(cell, s) + s * (1 + 4 + 1)
    assert got["input_mb_per_round"] == pytest.approx(want / 1e6, rel=1e-12)


def test_span_readers_without_program_spans(dose_run, monkeypatch):
    """An older program has no ``repro.telemetry``: no value, no error."""
    import repro
    cell, res, _ = dose_run
    monkeypatch.delattr(repro, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert readings(cell, res) == dict.fromkeys(NAMES)


SHARDED = """
import json, sys
sys.path.insert(0, {bench!r}); sys.path.insert(0, {src!r})
import jax
from fedbench import harness, spec
cell = spec.resolve(spec.load_benchmark(), "dose-s4-v56")
cell.name = "dose-s8-v56-shard4"
cell.traffic = spec.load_json(spec.BENCH_DIR / "traffic"
                              / "openkbp-noniid-s8-shard4.json")
cell.chips = 4
cell.config.update({tiny!r})
res = harness.run_cell(cell, 2 ** 33 + 77, 0.3, False, jax.devices())
r = harness.Reading(trace=None, cell=cell, rounds=res["attempted"],
                    window_s=0.0, chips=4, peaks=None, round_flops=0,
                    num_params=0)
print(json.dumps({{"attempted": res["attempted"],
                  "count": res["device"]["count"],
                  **{{n: spec.metric_reader(n)(r) for n in {names!r}}}}}))
"""


def test_span_readers_on_the_sharded_engine():
    """The 8-site sharded traffic on four virtual CPU devices, read in the
    child process that ran it (the device count is fixed when JAX
    starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(bench=str(BENCH), src=str(ROOT / "src"), tiny=TINY,
                          names=NAMES)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["count"] == 4 and got["attempted"] >= 1
    assert all(math.isfinite(got[n]) for n in NAMES), got
    assert got["chunk_lower_s"] > 0.0 and got["chunk_compile_s"] > 0.0
    cell = spec.resolve(spec.load_benchmark(), "dose-s4-v56")
    cell.config.update(TINY)
    cell.traffic = spec.load_json(BENCH / "traffic"
                                  / "openkbp-noniid-s8-shard4.json")
    sites = cell.traffic["sites"]
    # 2 sites per device; per slot lidx, pod (int32), w (float32), valid
    want = batch_bytes(cell, sites) + sites * (4 + 4 + 4 + 1)
    assert got["input_mb_per_round"] == pytest.approx(want / 1e6, rel=1e-12)
