"""The on-chip benchmark's harness (benchmarks/onchip), on the CPU.

Timings need the chip; what is checked here is the yardstick itself: the
trace reduction on a trace recorded on a TPU v5e, the FLOP and byte
counters against XLA and the shapes, that every cell finds its files by
name, that the run refuses without a TPU, and that ``correct`` holds for a
sound run and fails for the control and for each fault a cell can have,
at a size a test run holds.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "onchip"
sys.path.insert(0, str(BENCH))

from fedbench import compare, counts, harness, spec, tracing  # noqa: E402

TINY = dict(volume=[8, 8, 8], base_filters=4, num_levels=2)
TRACE = BENCH / "testdata" / "tiny.xplane.pb"


def tiny_cell(name: str) -> spec.Cell:
    cell = spec.resolve(spec.load_benchmark(), name)
    cell.config.update(TINY)
    return cell


def cpu_run(cell: spec.Cell, seed: int = 2 ** 33 + 5):
    import jax
    return harness.run_cell(cell, seed, 0.3, False,
                            jax.devices()[:cell.chips])


# ------------------------------------------------------------- counters


@pytest.mark.parametrize("name", ["sanet-dose-openkbp", "sanet-seg-brats"])
def test_flop_counter_sits_below_xla_cost_analysis(name):
    import jax
    import jax.numpy as jnp
    from repro.models.sanet import SANetConfig, sanet_apply, sanet_init
    cfg = spec.load_json(BENCH / "configs" / f"{name}.json")
    cfg.update(volume=[16, 16, 16], base_filters=8, num_levels=3)
    scfg = SANetConfig(in_channels=cfg["in_channels"],
                       out_channels=cfg["out_channels"],
                       base_filters=8, num_levels=3, task=cfg["task"])
    params = jax.eval_shape(lambda k: sanet_init(k, scfg),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 16, 16, 16, cfg["in_channels"]),
                             jnp.float32)
    cost = jax.jit(lambda p, v: sanet_apply(p, v, scfg)[0]).lower(
        params, x).compile().cost_analysis()
    xla = float((cost[0] if isinstance(cost, list) else cost)["flops"])
    ours = counts.forward_flops(cfg)
    # convolutions only: under XLA's count, which adds norms, SE and
    # elementwise work, by at most 10 %
    assert 0.9 * xla <= ours <= xla


@pytest.mark.parametrize("name", ["sanet-dose-openkbp", "sanet-seg-brats"])
def test_param_count_matches_the_model_and_the_file(name):
    import jax
    from repro.models.sanet import SANetConfig, sanet_init
    cfg = spec.load_json(BENCH / "configs" / f"{name}.json")
    scfg = SANetConfig(in_channels=cfg["in_channels"],
                       out_channels=cfg["out_channels"],
                       base_filters=cfg["base_filters"],
                       num_levels=cfg["num_levels"], task=cfg["task"])
    shapes = jax.eval_shape(lambda k: sanet_init(k, scfg),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert counts.num_params(cfg) == n == cfg["params"]


def test_fold_byte_counter_matches_its_shapes():
    s, n = 4, 6_844_323
    rows = np.zeros((s, n), np.float32)
    weights = np.zeros((s,), np.float32)
    out = np.zeros((n,), np.float32)
    assert counts.fold_bytes(n, s) == rows.nbytes + weights.nbytes + out.nbytes


# ------------------------------------------------------------- trace


def test_trace_reduction_on_a_chip_trace():
    t = tracing.load(str(TRACE))
    assert t.chips == [0]
    lo, hi = t.window
    assert hi > lo
    ops = t.window_ops(0)
    assert ops and all(lo <= o.start and o.end <= hi for o in ops)
    # busy time by a sweep of +1/-1 boundaries, independent of union()
    edges = sorted([(o.start, 1) for o in ops] + [(o.end, -1) for o in ops],
                   key=lambda e: (e[0], -e[1]))
    swept, depth, since = 0.0, 0, None
    for at, d in edges:
        if depth == 0 and d == 1:
            since = at
        depth += d
        if depth == 0:
            swept += at - since
    assert tracing.busy_ns(t, 0) == pytest.approx(swept, rel=1e-9)
    gaps = tracing.idle_gaps(t, n=10 ** 6)
    assert sum(g for _, g in gaps) * 1e9 == pytest.approx(
        tracing.window_ns(t) - swept, rel=1e-6)
    top = tracing.top_ops(t, n=10 ** 6)
    assert sum(s for _, s in top) * 1e9 == pytest.approx(
        sum(o.dur for o in ops), rel=1e-9)
    assert any("convolution" in o.category for o in ops)
    assert any("fedagg" in o.name for o in ops)


def test_per_layer_readers_on_a_chip_trace():
    t = tracing.load(str(TRACE))
    cell = tiny_cell("dose-s4-v56")
    peaks = harness.peaks_for("TPU v5 lite")
    r = harness.Reading(trace=t, cell=cell, rounds=3,
                        window_s=tracing.window_ns(t) / 1e9, chips=1,
                        peaks=peaks,
                        round_flops=counts.round_flops(cell.config,
                                                       cell.traffic),
                        num_params=counts.num_params(cell.config))
    got = {m["name"]: spec.metric_reader(m["name"])(r)
           for m in cell.per_layer}
    assert 0.0 <= got["device_idle_share"] < 100.0
    assert 0.0 < got["mfu"] < 100.0
    assert got["conv_ms_per_round"] > 0.0
    assert 0.0 < got["fedagg_roofline"] <= 105.0


# ------------------------------------------------------------- the spec


def test_every_cell_resolves_its_files_and_names():
    bench = spec.load_benchmark()
    assert spec.check_names(bench) == []
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert callable(spec.metric_reader(m["name"]))
    for name in cells:
        cell = spec.resolve(bench, name)
        assert cell.config and cell.traffic and cell.limits
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert cell.per_layer
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert set(c["reduced"]) <= set(spec.load_json(ROOT / c["file"])
                                        ["reduced"])


def test_a_new_cell_needs_only_new_files(tmp_path):
    bench_dir = tmp_path / "onchip"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    traffic = dict(spec.load_json(bench_dir / "traffic"
                                  / "openkbp-noniid-s4.json"),
                   name="openkbp-noniid-s2", sites=2, site_cases=[48, 12])
    (bench_dir / "traffic" / "openkbp-noniid-s2.json").write_text(
        json.dumps(traffic))
    (bench_dir / "limits" / "dose-s2-v56.json").write_text(json.dumps(
        spec.load_json(bench_dir / "limits" / "dose-s4-v56.json")))
    bench["workloads"].append({"name": "dose-s2-v56",
                               "config": "sanet-dose-openkbp",
                               "traffic": "openkbp-noniid-s2", "chips": 1,
                               "why": "two sites"})
    cell = spec.resolve(bench, "dose-s2-v56", bench_dir=bench_dir)
    assert cell.traffic["sites"] == 2
    assert {p: p.read_bytes() for p in before} == before


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "dose-s4-v56", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for path in spec.load_benchmark()["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/onchip/run.py",
                        "--workload", "dose-s4-v56", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, cwd=tmp_path,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ------------------------------------------------------------- correct


def test_sound_run_is_correct():
    res = cpu_run(tiny_cell("dose-s4-v56"))
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert set(res["compared"]) == {"loss0_gap", "update_gap_median"}


def _unchanged_state(monkeypatch):
    from repro.core import federation as F
    orig = F.build_fl_round

    def build(ctx, remat_local=False):
        fl_round = orig(ctx, remat_local)

        def same(fl_state, batches, round_inputs):
            new, metrics = fl_round(fl_state, batches, round_inputs)
            return {**fl_state, "round": new["round"]}, metrics
        return same
    monkeypatch.setattr(F, "build_fl_round", build)


def _half_the_sites(monkeypatch):
    import jax.numpy as jnp
    from repro.core.agg_engine import AggregationEngine

    def half(self, flat, weights):
        k = flat.shape[0] // 2
        w = weights[:k] / jnp.sum(weights[:k])
        return jnp.einsum("s,sn->n", w, flat[:k])
    monkeypatch.setattr(AggregationEngine, "reduce_flat", half)


def _altered_loss(monkeypatch):
    from repro.models import sanet
    orig = sanet.dose_loss

    def altered(params, batch, cfg, **kw):
        loss, aux = orig(params, batch, cfg, **kw)
        return loss * 1.01, aux
    monkeypatch.setattr(sanet, "dose_loss", altered)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_sites,
                                   _altered_loss],
                         ids=["state_unchanged", "half_the_batch",
                              "answer_altered"])
def test_a_broken_step_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = cpu_run(tiny_cell("dose-s4-v56"))
    assert res["correct"] is False, res["compared"]


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
out = {{}}
for name in ("sound", "exchange_left_out"):
    if name == "exchange_left_out":
        jax.lax.psum = lambda x, axis_name, **kw: x
    res = harness.run_cell(cell, 77, 0.3, False, jax.devices())
    out[name] = {{"correct": res["correct"], "compared": res["compared"],
                 "count": res["device"]["count"]}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_runs():
    """The 8-site sharded traffic (the four-chip cell this benchmark
    leaves for a later PR) on four virtual CPU devices, sound and with
    the fold's exchange between devices left out, in one child process
    (the device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(bench=str(BENCH), src=str(ROOT / "src"), tiny=TINY)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", ["sound", "exchange_left_out"])
def test_sharded_cell_on_four_virtual_devices(name, sharded_runs):
    res = sharded_runs[name]
    assert res["count"] == 4
    assert res["correct"] is (name == "sound"), res["compared"]


@pytest.mark.parametrize("name", ["dose-s4-v56", "seg-s4-v56"])
def test_lower_precision_fails_the_limits(name):
    """A bfloat16 reference in the program's place, against the float32
    one on the same cases, at a size a test run holds."""
    from fedbench.pool import CasePool
    cell = tiny_cell(name)
    s = harness.seeds(2 ** 34 + 3)
    tr = cell.traffic
    pool = CasePool(cell.config, tr["site_cases"], s["pool_key"], s["draw"])
    ref = harness.reference_numbers(cell, pool, s["job"], 3, "highest")
    low = harness.reference_numbers(cell, pool, s["job"], 3, "bf16")
    numbers = harness.numbers_compared(low, ref)
    assert not compare.all_within(compare.judge(numbers, cell.limits))
