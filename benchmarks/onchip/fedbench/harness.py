"""One run of one cell: set-up, the measured window, the check of ``correct``.

The window drives the program's own entry point, ``FederatedJob.run`` on
the stacked transport (the scan engine, or the sharded engine for a cell
whose traffic says ``"engine": "sharded"``).  The benchmark hands the job
its case pool through the task's ``build()`` and times the job through
the recorder the job returns from ``recorder()``:

* set-up is everything up to the end of the first chunk of rounds whose
  end lies at or past the rounds the reference follows: process start,
  the pool, the weights, compiling or loading the chunk program and the
  first chunk itself;
* the window then runs from that chunk's end to the end of the first
  whole chunk that ends ``--seconds`` or more later, host work between
  chunks included (the sharded engine steps round by round, so there a
  chunk is one round), and the job is stopped there;
* ``rounds_per_s`` is the rounds completed in the window over its length.

Once the window has closed and the device's peak memory has been read,
the job's state is gone and the reference re-runs the first rounds from
the same seed on the same cases, on the first chip alone whatever the
cell's chip count (its site step then compiles once); the numbers
compared are in :mod:`fedbench.compare`.  Everything that depends on the
model comes from the configuration's family (``families/<family>.py``,
:func:`fedbench.spec.family`).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from fedbench import compare, spec

PROCESS_T0 = time.perf_counter()
CACHE_DIR = spec.ROOT / ".jax_cache"
MAX_ROUNDS_PER_S = 200      # the job is sized for this rate, then stopped


class Refused(SystemExit):
    """The run cannot be made here; no result is printed."""


class WindowClosed(Exception):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- device


def chip_devices(chips: int):
    """The first ``chips`` TPU devices, or a refusal."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"run.py: needs a TPU, but JAX found platform "
                      f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < chips:
        raise Refused(f"run.py: this cell needs {chips} chips, found "
                      f"{len(devs)}")
    return devs[:chips]


def enable_cache(cache_dir: Path = CACHE_DIR) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever ``JAX_COMPILATION_CACHE_DIR`` says: only the checkout is the
    run's own, and the path is part of the cache's key.  No size limit:
    one set by ``JAX_COMPILATION_CACHE_MAX_SIZE`` refuses every entry
    larger than itself, and a cell's programs are."""
    import jax
    cache_dir.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@contextlib.contextmanager
def captured_compiles(into: List[Any]):
    """Keep every program compiled ahead of time inside the block, for
    its ``memory_analysis``; nothing else about the compile changes."""
    import jax
    orig = jax.stages.Lowered.compile

    def compile_and_keep(self, *a, **kw):
        out = orig(self, *a, **kw)
        into.append(out)
        return out

    jax.stages.Lowered.compile = compile_and_keep
    try:
        yield
    finally:
        jax.stages.Lowered.compile = orig


def memory_line(devices, compiled: List[Any]) -> str:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    parts = [f"memory: peak_bytes_in_use={peaks}"]
    for c in compiled:
        try:
            m = c.memory_analysis()
        except Exception as e:  # a backend without the analysis
            parts.append(f"memory_analysis unavailable ({e})")
            continue
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        parts.append(
            f"compiled argument+output+temp-alias={need} "
            f"(argument={m.argument_size_in_bytes} "
            f"output={m.output_size_in_bytes} temp={m.temp_size_in_bytes} "
            f"alias={m.alias_size_in_bytes})")
    return "; ".join(parts)


# ---------------------------------------------------------------- the job


def seeds(seed: int) -> Dict[str, Any]:
    """Everything the run draws, from one ``--seed`` of any size: the
    pool's key, its draw order, and the job's seed (the program keys its
    weights with ``PRNGKey(job seed)``, which keeps 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(4)
    return {"pool_key": words[:2], "draw": int(words[2]),
            "job": int(words[3] & 0x7FFFFFFF)}


class Window:
    """The harness's clock and what it keeps of the first rounds."""

    def __init__(self, first_rounds: int, chunk: int, seconds: float,
                 trace_dir: Optional[str]):
        self.first = first_rounds
        self.chunk = chunk
        self.warm = chunk * -(-first_rounds // chunk)
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.losses: List[np.ndarray] = []
        self.state = None            # host carry after the warm-up chunk
        self.global_params = None    # host global after the first rounds
        self.window_losses: List[np.ndarray] = []
        self.t_start = self.t_end = None
        self.rounds = 0
        self._span = None

    def on_round(self, r: int, per_site_loss, global_fn) -> None:
        loss = np.asarray(per_site_loss, np.float64).reshape(-1)
        if r < self.first:
            self.losses.append(loss)
        elif r >= self.warm:
            self.window_losses.append(loss)
        if r == self.first - 1 and global_fn is not None:
            import jax
            self.global_params = jax.device_get(global_fn())

    def on_state(self, r: int, state_fn) -> None:
        if r == self.warm - 1:
            self.state = state_fn()

    def on_chunk_end(self, r: int) -> None:
        import jax
        now = time.perf_counter()
        if r + 1 == self.warm:
            self.t_start = now
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                self._span = jax.profiler.TraceAnnotation("onchip.window")
                self._span.__enter__()
                self.t_start = time.perf_counter()
            return
        if r + 1 > self.warm and now - self.t_start >= self.seconds:
            self.t_end = now
            self.rounds = r + 1 - self.warm
            if self._span is not None:
                self._span.__exit__(None, None, None)
                jax.profiler.stop_trace()
            raise WindowClosed()


def bench_classes():
    """The job's own classes with the benchmark's two seams: the task's
    ``build()`` serves the case pool, the job's ``recorder()`` is timed."""
    from repro.api import FederatedJob, TaskConfig
    from repro.core.session import RoundRecorder

    @dataclasses.dataclass(frozen=True)
    class PoolTask(TaskConfig):
        pool: Any = dataclasses.field(default=None, compare=False,
                                      repr=False)

        def build(self):
            import jax
            bundle = super().build()
            pool, batch = self.pool, self.batch

            def sample(site, step):
                with jax.profiler.TraceAnnotation("onchip.pool.sample"):
                    return pool.sample(site, step, batch)

            def stacked(rnd, k):
                with jax.profiler.TraceAnnotation("onchip.pool.stacked"):
                    return pool.stacked(rnd, k, batch)

            return dataclasses.replace(
                bundle, sample=sample, stacked=stacked, traced_stacked=None,
                init_fn=jax.jit(bundle.init_fn))

    class NullStore:
        """Stands where a checkpoint store would, so that the sharded
        engine hands its global to the recorder on every round; it
        writes nothing."""

        def save(self, *a, **kw):
            pass

    class TimedRecorder(RoundRecorder):
        def __init__(self, rounds, num_sites, win: Window, per_round: bool):
            super().__init__(rounds, num_sites=num_sites)
            self.win, self.per_round = win, per_round
            if per_round:
                self.store = NullStore()

        def record(self, round_index, per_site_loss, active, global_fn=None,
                   extra=None):
            super().record(round_index, per_site_loss, active, None, extra)
            self.win.on_round(round_index, per_site_loss, global_fn)
            if self.per_round:
                self.win.on_chunk_end(round_index)

        def save_state(self, round_index, state_fn, meta=None):
            self.win.on_state(round_index, state_fn)
            self.win.on_chunk_end(round_index)

    @dataclasses.dataclass
    class TimedJob(FederatedJob):
        window: Any = None

        def recorder(self, rounds, num_sites):
            return TimedRecorder(rounds, num_sites, self.window,
                                 per_round=self.shard_sites)

    return PoolTask, TimedJob


def build_job(cell: spec.Cell, pool, job_seed: int, rounds: int,
              win: Window):
    PoolTask, TimedJob = bench_classes()
    cfg, tr = cell.config, cell.traffic
    opt = cfg["optimizer"]
    sharded = tr["engine"] == "sharded"
    task = PoolTask(pool=pool,
                    **cell.family.task_fields(cfg, tr, job_seed))
    return TimedJob(
        task=task, strategy=tr["strategy"], rounds=rounds,
        local_steps=tr["local_steps"], lr=opt["lr"],
        weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"],
        case_counts=tuple(tr["site_cases"]), transport="stacked",
        scheduler=tr["scheduler"],
        round_engine="auto" if sharded else "scan",
        chunk_rounds=None if sharded else tr["chunk_rounds"],
        shard_sites=sharded, ckpt_every=1 if sharded else 10**9,
        seed=job_seed, window=win)


# ---------------------------------------------------------------- run


def program_numbers(win: Window, cell: spec.Cell, init) -> Dict[str, Any]:
    """What the program produced over the first rounds, as the reference
    reports its own: losses, and per leaf path the norm of the global's
    change from ``init`` where the engine exposed it."""
    out: Dict[str, Any] = {"losses": np.stack(win.losses)}
    glob = win.global_params
    if win.state is not None:
        # every site holds the broadcast global after a fedavg round
        w = np.asarray(cell.traffic["site_cases"], np.float64)
        glob = _tree_map(lambda x: np.tensordot(w / w.sum(), np.asarray(
            x, np.float64), axes=1), win.state["params"])
    if glob is not None:
        out["change"] = compare.path_norms(_tree_map(
            lambda g, i: np.asarray(g, np.float64) - np.asarray(i, np.float64),
            glob, init))
    return out


def _tree_map(fn, *trees):
    import jax
    return jax.tree.map(fn, *trees)


def aligned(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]):
    """Two ``{leaf path: norm}`` maps as arrays in one leaf order."""
    if set(got) != set(want):
        raise ValueError("program and reference trees differ: "
                         f"{sorted(set(got) ^ set(want))[:5]}")
    keys = sorted(want)
    return (np.stack([got[k] for k in keys], axis=-1),
            np.stack([want[k] for k in keys], axis=-1))


def reference_numbers(cell: spec.Cell, pool, job_seed: int, rounds: int,
                      precision: str, device) -> Dict[str, Any]:
    """The reference's first ``rounds`` rounds on the cell's cases, every
    site on ``device``."""
    import jax
    tr = cell.traffic
    batch_fn = lambda s, step: pool.sample(s, step, tr["batch"])
    return cell.family.run_reference(
        cell.config, cell.config["optimizer"], tr["site_cases"], batch_fn,
        jax.random.PRNGKey(job_seed), rounds, tr["local_steps"], precision,
        device)


def numbers_compared(got: Dict[str, Any], ref: Dict[str, Any]
                     ) -> Dict[str, float]:
    """The gaps between ``got`` (the program, or the control) and the
    reference, leaves aligned by path."""
    _, g0 = aligned(ref["grad0"], ref["grad0"])
    p: Dict[str, Any] = {"losses": got["losses"]}
    r: Dict[str, Any] = {"losses": ref["losses"], "grad0_norms": g0}
    if "change" in got:
        p["change_norms"], r["change_norms"] = aligned(got["change"],
                                                       ref["change"])
    return compare.gaps(p, r)


def peaks_for(kind: str) -> Dict[str, Any]:
    table = spec.load_json(spec.BENCH_DIR / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets."""
    trace: Any
    cell: spec.Cell
    rounds: int
    window_s: float
    chips: int
    peaks: Dict[str, Any]
    round_flops: int
    num_params: int


def per_layer(cell: spec.Cell, reading: Reading) -> Dict[str, Any]:
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"])(reading)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices) -> Dict[str, Any]:
    """Set up, measure, check; returns the result line's object."""
    import jax
    cfg, tr = cell.config, cell.traffic
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if devices[0].platform == "tpu" else None
    s = seeds(seed)
    first = int(tr["first_rounds"])
    chunk = 1 if tr["engine"] == "sharded" else int(tr["chunk_rounds"])
    trace_dir = tempfile.mkdtemp(prefix="onchip-trace-") if trace else None
    win = Window(first, chunk, seconds, trace_dir)
    if chunk != 1 and win.warm != first:
        raise ValueError("a scan cell's chunk_rounds must divide its "
                         "first_rounds: the engine hands over its state only "
                         "at chunk ends")
    rounds = win.warm + chunk * math.ceil(seconds * MAX_ROUNDS_PER_S / chunk)
    compiled: List[Any] = []
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        pool = cell.family.make_pool(cfg, tr["site_cases"], s["pool_key"],
                                     s["draw"])
        job = build_job(cell, pool, s["job"], rounds, win)
        cell.family.check_model(cfg, job.task.model_config())
        try:
            with captured_compiles(compiled):
                job.run()
        except WindowClosed:
            pass
    if win.t_end is None:
        raise RuntimeError(f"the job's {rounds} rounds ended before the "
                           f"{seconds} s window closed")
    setup_s = win.t_start - PROCESS_T0
    window_s = win.t_end - win.t_start
    log(f"set-up {setup_s:.3f} s; window {window_s:.3f} s, "
        f"{win.rounds} rounds; pool {pool.nbytes} bytes")
    mem = memory_line(devices, compiled)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    del job, compiled
    gc.collect()

    reading = None
    if trace:
        from fedbench import tracing
        t = tracing.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        reading = Reading(trace=t, cell=cell, rounds=win.rounds,
                          window_s=tracing.window_ns(t) / 1e9,
                          chips=len(devices), peaks=peaks,
                          round_flops=cell.family.round_flops(cfg, tr),
                          num_params=cell.family.num_params(cfg))

    t_ref = time.perf_counter()
    ref = reference_numbers(cell, pool, s["job"], first, "highest",
                            devices[0])
    log(f"reference {time.perf_counter() - t_ref:.3f} s (first step, "
        f"compile included, {ref['seconds'][0]:.3f} s)")
    prog = program_numbers(win, cell, ref["init"])
    compared = compare.judge(numbers_compared(prog, ref), cell.limits)
    window_loss = (np.stack(win.window_losses) if win.window_losses
                   else np.zeros((0, tr["sites"])))
    failed = int(np.sum(~np.all(np.isfinite(window_loss), axis=1)))
    correct = compare.all_within(compared) and failed == 0

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": int(win.rounds),
                              "failed": failed}
    if trace:
        from fedbench import tracing
        result["metrics"] = per_layer(cell, reading)
        device.update(busy_s=tracing.mean_busy_s(reading.trace),
                      window_s=reading.window_s)
        result["device"] = device
        result["breakdown"] = {"device_ops": tracing.top_ops(reading.trace),
                               "idle_gaps": tracing.idle_gaps(reading.trace)}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {
            "rounds_per_s": {"value": win.rounds / window_s,
                             "unit": units["rounds_per_s"]},
            "setup_s": {"value": setup_s, "unit": units["setup_s"]}}
        result["device"] = device
    result["compared"] = compared
    print(mem, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (spec.ROOT / "src" / "repro" / "api.py").is_file():
        raise Refused("run.py: the program (src/repro) is not in this "
                      f"checkout ({spec.ROOT})")
    sys.path.insert(0, str(spec.ROOT / "src"))
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    devices = chip_devices(cell.chips)
    enable_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    for name, c in result["compared"].items():
        log(f"compared {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
