"""The compiled round engine (ISSUE 4 tentpole): scan↔loop parity across
strategies and codecs, donation safety, chunking invariance, compile-time
accounting, on-device round inputs, and the buffered arrival loop as
device state."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FederatedJob, TaskConfig
from repro.core.round_engine import chunk_plan
from repro.core.session import BufferedScheduler


def _job(**kw):
    base = dict(
        task=TaskConfig(kind="tokens", arch="smollm-135m", sites=4, batch=2,
                        seq=16, heterogeneity=0.3, seed=0),
        strategy="fedavg", rounds=3, lr=1e-3, seed=0)
    base.update(kw)
    return FederatedJob(**base)


def _assert_trees_close(a, b, rtol=1e-4, atol=1e-6):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Parity: the scan engine vs the retired per-round loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ["fedavg", "fedprox", "gcml"])
def test_scan_matches_loop(strategy):
    """Same seed ⇒ same globals AND same per-round losses, with churn:
    the scan consumes the identical masks/pairings/batches, so fusing K
    rounds into one program must not change the math."""
    job = _job(strategy=strategy, max_dropout=1)
    loop = job.replace(round_engine="loop").run()
    scan = job.replace(round_engine="scan").run()
    _assert_trees_close(loop.global_params, scan.global_params)
    np.testing.assert_allclose(loop.losses, scan.losses, rtol=1e-4)
    if strategy == "gcml":              # pairing history must match too
        for hl, hs in zip(loop.history, scan.history):
            assert hl["partner"] == hs["partner"]
            assert hl["is_receiver"] == hs["is_receiver"]


@pytest.mark.parametrize("strategy", ["pooled", "individual"])
def test_scan_matches_loop_baselines(strategy):
    job = _job(strategy=strategy, rounds=2)
    loop = job.replace(round_engine="loop").run()
    scan = job.run()                    # auto resolves to the scan engine
    _assert_trees_close(loop.global_params, scan.global_params)


def test_scan_matches_loop_compressed_int8():
    """The on-device codec replicates the wire codec's per-leaf chunk
    layout, so quantized-global parity holds at the same tolerance the
    stacked↔thread test uses — and the simulated byte accounting is
    byte-identical."""
    job = _job(compression="int8", rounds=3)
    loop = job.replace(round_engine="loop").run()
    scan = job.replace(round_engine="scan").run()
    _assert_trees_close(loop.global_params, scan.global_params,
                        rtol=2e-3, atol=1e-4)
    assert scan.comm["upload_bytes"] == loop.comm["upload_bytes"]
    assert scan.comm["upload_raw_bytes"] == loop.comm["upload_raw_bytes"]
    assert scan.comm["upload_raw_bytes"] >= 3 * scan.comm["upload_bytes"]
    assert [h["upload_bytes"] for h in scan.history] == \
        [h["upload_bytes"] for h in loop.history]


def test_scan_matches_loop_compressed_fp8():
    """fp8's e4m3 cast can flip near-tie bins between the numpy and XLA
    converters, so parity is behavioral (per-element within one coarse
    fp8 quantization step), not bitwise like int8."""
    job = _job(compression="fp8", rounds=2)
    loop = job.replace(round_engine="loop").run()
    scan = job.replace(round_engine="scan").run()
    _assert_trees_close(loop.global_params, scan.global_params,
                        rtol=5e-2, atol=1e-3)


def test_scan_matches_loop_buffered():
    """The traced arrival loop replays the retired loop's order stream,
    discounts and K-of-S finalizations — versions match round for round."""
    job = _job(scheduler=BufferedScheduler(buffer_k=2), rounds=4)
    loop = job.replace(round_engine="loop").run()
    scan = job.replace(round_engine="scan").run()
    _assert_trees_close(loop.global_params, scan.global_params,
                        rtol=1e-4, atol=1e-5)
    assert [h["version"] for h in loop.history] == \
        [h["version"] for h in scan.history]
    assert all("step_s" in h for h in scan.history)
    assert all("step_s" in h for h in loop.history)   # satellite fix


def test_scan_matches_loop_buffered_int8():
    """Buffered + quantized deltas: the decode-reference ring lives on
    device; the flat chunk layout differs from the per-leaf wire layout,
    so parity is behavioral (close globals, ≥3× byte ratio)."""
    job = _job(scheduler=BufferedScheduler(buffer_k=2), compression="int8",
               rounds=4)
    loop = job.replace(round_engine="loop").run()
    scan = job.replace(round_engine="scan").run()
    _assert_trees_close(loop.global_params, scan.global_params,
                        rtol=5e-3, atol=5e-4)
    assert scan.comm["upload_count"] == loop.comm["upload_count"]
    assert scan.comm["upload_raw_bytes"] >= 3 * scan.comm["upload_bytes"]


def test_scan_matches_loop_compressed_fedprox():
    """ROADMAP gap closed: the stacked compressed path is no longer
    fedavg-only — fedprox runs its local half (``fedprox-local``) with
    the proximal anchor re-pinned to each broadcast global, on both the
    loop and the scan, with byte-identical accounting."""
    job = _job(strategy="fedprox", compression="int8", rounds=3)
    loop = job.replace(round_engine="loop").run()
    scan = job.replace(round_engine="scan").run()
    _assert_trees_close(loop.global_params, scan.global_params,
                        rtol=2e-3, atol=1e-4)
    assert scan.comm["upload_bytes"] == loop.comm["upload_bytes"]
    assert scan.comm["compression"] == "int8"


def test_compressed_fedprox_prox_actually_pulls():
    """The proximal term must bite on the compressed path: a large mu
    anchors local training to the broadcast global, so the federation
    drifts less from its initialization than with mu=0."""
    from repro.core import federation as F
    # local_steps > 1: with a single step sites sit exactly at the
    # anchor, where the proximal gradient vanishes
    base = _job(strategy="fedprox", compression="int8", rounds=3,
                local_steps=3, lr=5e-3)
    tight = base.replace(prox_mu=50.0).run()
    loose = base.replace(prox_mu=0.0).run()
    ctx = base.context()
    init = F.global_model(
        F.init_fl_state(ctx, base.task.build().init_fn,
                        jax.random.PRNGKey(base.seed)), ctx)

    def dist(res):
        return float(sum(
            jnp.sum(jnp.square(jnp.asarray(np.asarray(g), jnp.float32)
                               - i.astype(jnp.float32)))
            for g, i in zip(jax.tree.leaves(res.global_params),
                            jax.tree.leaves(init))))
    assert dist(tight) < dist(loose)


def test_topk_fixed_compiles_under_scan():
    """The fixed-k sparsifier runs on the scan engine (the data-shaped
    ``topk-sparse`` still takes the host loop): byte accounting matches
    the wire codec round for round (dense bootstrap, then 8·k per leaf),
    and the run trains."""
    job = _job(compression="topk-fixed", rounds=4, lr=5e-3)
    loop = job.replace(round_engine="loop").run()
    scan = job.replace(round_engine="scan").run()     # must NOT fall back
    assert [h["upload_bytes"] for h in scan.history] == \
        [h["upload_bytes"] for h in loop.history]
    assert scan.history[1]["upload_bytes"] < scan.history[0]["upload_bytes"]
    assert np.isfinite(scan.losses).all()
    assert scan.final_loss < scan.losses[0]
    # selection ties differ between argpartition and lax.top_k, so
    # parity is behavioral: overwhelmingly-equal globals + equal bytes
    mism = tot = 0
    for x, y in zip(jax.tree.leaves(loop.global_params),
                    jax.tree.leaves(scan.global_params)):
        bad = ~np.isclose(np.asarray(x), np.asarray(y), rtol=5e-2, atol=1e-3)
        mism += int(bad.sum())
        tot += bad.size
    assert mism / tot < 0.01
    # sparse rounds really are sparse: ~10% of entries at 8 B each vs
    # dense fp32 (the run total includes the dense bootstrap round)
    assert scan.history[1]["upload_bytes"] * 4 < scan.history[0]["upload_bytes"]
    assert scan.comm["upload_raw_bytes"] > 2 * scan.comm["upload_bytes"]


def test_topk_sparse_still_falls_back():
    with pytest.raises(ValueError, match="scan"):
        _job(compression="topk-sparse", round_engine="scan").run()


@pytest.mark.parametrize("kind,extra", [
    ("dose", {}), ("seg", {"in_channels": 2, "num_classes": 3})])
def test_device_data_volume_tasks(kind, extra):
    """Satellite: traced jnp dose/seg generators — device_data=True now
    covers the SA-Net tasks, zero per-round host batch generation."""
    job = FederatedJob(
        task=TaskConfig(kind=kind, sites=3, batch=2, volume=(16, 16, 16),
                        heterogeneity=0.3, seed=0, **extra),
        strategy="fedavg", rounds=3, lr=3e-3, seed=0, device_data=True)
    res = job.run()
    assert np.isfinite(res.losses).all()
    assert res.final_loss < res.losses[0]


def test_traced_volume_generators_match_host_shapes():
    from repro.data.synthetic import DoseTaskGenerator, SegTaskGenerator
    dg = DoseTaskGenerator(volume=(8, 8, 8), num_oars=2, num_sites=3,
                           heterogeneity=0.4)
    host = dg.stacked_batches(0, 2, 2)
    dev = dg.traced_stacked_batches(jax.random.PRNGKey(0), 2, 2)
    assert set(host) == set(dev)
    for k in host:
        assert host[k].shape == tuple(dev[k].shape), k
    # the analytic dose law holds on device too: normalized, body-masked
    dose = np.asarray(dev["dose"])
    assert 0.0 <= dose.min() and dose.max() <= 1.0 + 1e-6
    assert (np.asarray(dev["mask"]) == np.asarray(host["mask"])).all()
    sg = SegTaskGenerator(volume=(8, 8, 8), in_channels=2, num_classes=3,
                          num_sites=2)
    hs = sg.stacked_batches(0, 1, 2)
    ds = sg.traced_stacked_batches(jax.random.PRNGKey(1), 1, 2)
    for k in hs:
        assert hs[k].shape == tuple(ds[k].shape), k
    labs = np.asarray(ds["labels"])
    assert labs.min() >= 0 and labs.max() < 3 and labs.dtype == np.int32


def test_scan_matches_loop_dose_task():
    """Volume tasks have no traced generator — host-generated batches
    still ride the compiled scan, chunk-transferred."""
    job = FederatedJob(
        task=TaskConfig(kind="dose", sites=3, batch=2, volume=(16, 16, 16),
                        heterogeneity=0.3, seed=0),
        strategy="fedavg", rounds=2, seed=0)
    loop = job.replace(round_engine="loop").run()
    scan = job.run()
    _assert_trees_close(loop.global_params, scan.global_params)


# ---------------------------------------------------------------------------
# Chunking, donation, compile accounting
# ---------------------------------------------------------------------------


def test_chunking_invariance():
    """Chunk size is an execution knob, not a semantic one."""
    job = _job(rounds=5)
    ref = job.replace(chunk_rounds=5).run()
    for ck in (1, 2, 3):
        res = job.replace(chunk_rounds=ck).run()
        _assert_trees_close(ref.global_params, res.global_params)
        np.testing.assert_allclose(ref.losses, res.losses, rtol=1e-5)


def test_chunk_plan_alignment():
    assert chunk_plan(20, 8) == [8, 8, 4]
    assert chunk_plan(3, None) == [3]
    assert sum(chunk_plan(100, None)) == 100
    # with checkpointing every 10 rounds, a boundary follows rounds 0/10
    plan = chunk_plan(20, 8, ckpt_every=10)
    ends = np.cumsum(plan)
    assert 1 in ends and 11 in ends and ends[-1] == 20


def test_no_use_after_donate():
    """The carry is donated into every chunk; the returned state must be
    the live one (readable, reusable) even after multiple chunks."""
    job = _job(rounds=4, chunk_rounds=2)
    res = job.run()
    assert res.state is not None
    for leaf in jax.tree.leaves(res.state["params"]):
        assert np.isfinite(np.asarray(leaf)).all()
    # the recorded global equals the state's aggregate (nothing stale)
    from repro.core import federation as F
    ctx = job.context()
    _assert_trees_close(res.global_params, F.global_model(res.state, ctx))


def test_compile_time_reported_separately():
    """Satellite: round 0's step_s no longer absorbs jit compilation —
    on both engines compile_s is reported on the JobResult and step_s
    stays in steady-state range."""
    for engine in ("scan", "loop"):
        res = _job(rounds=3, round_engine=engine).run()
        assert res.compile_s > 0.0
        steps = [h["step_s"] for h in res.history]
        assert max(steps) < res.compile_s      # compile dwarfs a tiny step
        assert res.to_dict()["compile_s"] == res.compile_s


def test_checkpointing_on_scan_engine(tmp_path):
    job = _job(rounds=4, chunk_rounds=4, ckpt_every=2,
               checkpoint_dir=str(tmp_path))
    res = job.run()
    assert np.isfinite(res.final_loss)
    saved = sorted(p.name for p in tmp_path.glob("global_round*.npz"))
    assert saved                        # rounds 0 and 2 materialized
    assert (tmp_path / "manifest.json").exists()


# ---------------------------------------------------------------------------
# On-device round inputs (traced masks / pairings / batches)
# ---------------------------------------------------------------------------


def test_device_data_trains():
    job = _job(rounds=6, lr=5e-3, device_data=True)
    res = job.run()
    assert np.isfinite(res.losses).all()
    assert res.final_loss < res.losses[0]
    assert res.comm["upload_count"] == 6 * 4    # all sites active


def test_device_data_with_churn_and_gossip():
    # odd site count: the traced pairing must leave one site out cleanly
    job = _job(task=TaskConfig(kind="tokens", arch="smollm-135m", sites=5,
                               batch=2, seq=16, heterogeneity=0.3, seed=0),
               strategy="gcml", rounds=4, max_dropout=2, device_data=True)
    res = job.run()
    assert np.isfinite(np.asarray(res.losses)).all()
    for h in res.history:
        assert 3 <= h["active"] <= 5            # S − N_max bound holds
        # receivers always have a distinct partner assigned
        for i, is_r in enumerate(h["is_receiver"]):
            if is_r:
                assert h["partner"][i] != i


def test_device_data_unsupported_combos_raise():
    with pytest.raises(ValueError, match="device_data"):
        _job(device_data=True, compression="int8").run()
    with pytest.raises(ValueError, match="device_data"):
        _job(device_data=True, scheduler=BufferedScheduler(buffer_k=2)).run()
    # volume tasks now have traced generators — EXCEPT with site_pools,
    # whose case recycling indexes by host step
    with pytest.raises(ValueError, match="device_data"):
        FederatedJob(task=TaskConfig(kind="dose", sites=2, batch=1,
                                     volume=(16, 16, 16),
                                     site_pools=(2, 2)),
                     rounds=1, device_data=True).run()
    # pod-tier churn needs the host-precomputed schedule
    with pytest.raises(ValueError, match="pod_dropout"):
        _job(device_data=True, topology="pods:2", pod_dropout=1).run()


@pytest.mark.parametrize("sites", [5, 6])   # odd counts sit one site out
def test_traced_round_inputs_laws(sites):
    """Traced Algorithm-2 churn and gossip pairing respect the host
    invariants: dropout bounded by N_max, pairings are disjoint
    sender/receiver sets among active sites."""
    from repro.core.dropout import availability_step_traced
    from repro.core.gossip import pair_sites_traced
    key = jax.random.PRNGKey(0)
    active = jnp.ones((sites,), bool)
    for r in range(30):
        active = availability_step_traced(jax.random.fold_in(key, r),
                                          active, 2)
        a = np.asarray(active)
        assert sites - 2 <= a.sum() <= sites
    for r in range(10):
        k = jax.random.fold_in(key, 100 + r)
        partner, is_recv, is_send = (np.asarray(x) for x in
                                     pair_sites_traced(k, active))
        a = np.asarray(active)
        assert not (is_recv & is_send).any()
        assert is_recv.sum() == is_send.sum() <= a.sum() // 2
        assert (a[partner[is_recv]]).all()      # senders are active
        assert set(partner[is_recv]) == set(np.flatnonzero(is_send))


# ---------------------------------------------------------------------------
# Engine selection surface
# ---------------------------------------------------------------------------


def test_round_engine_scan_raises_on_unsupported():
    with pytest.raises(ValueError, match="scan"):
        _job(compression="topk-sparse", round_engine="scan").run()


def test_round_engine_unknown_name():
    with pytest.raises(ValueError, match="round_engine"):
        _job(round_engine="bogus").run()


def test_topk_and_wide_staleness_fall_back_to_loop():
    res = _job(compression="topk-sparse", rounds=2).run()
    assert np.isfinite(res.final_loss)
    sched = BufferedScheduler(buffer_k=2, max_staleness=64)
    res = _job(scheduler=sched, compression="int8", rounds=2).run()
    assert np.isfinite(res.final_loss)


def test_train_cli_chunk_rounds_flag():
    from repro.launch.train import make_parser
    args = make_parser().parse_args(["--chunk-rounds", "4"])
    assert args.chunk_rounds == 4
    assert args.round_engine == "auto"
    assert make_parser().parse_args([]).chunk_rounds is None


# ---------------------------------------------------------------------------
# Sharded stacked simulator (ISSUE 8 tentpole): the [S, …] site state
# partitioned over the ("site",) mesh must reproduce the dense engine.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw,rtol", [
    (dict(), 1e-5),                                      # fedavg
    (dict(strategy="fedprox"), 1e-5),                    # proximal anchor
    (dict(topology="pods:2"), 1e-5),                     # two-tier fold
    (dict(compression="int8"), 1e-4),                    # qdq + EF residual
    (dict(compression="int8", strategy="fedprox"), 1e-4),
    (dict(sample="uniform:2", dropout_scenario="shutdown"), 1e-5),
    (dict(sample="poisson:0.6", max_dropout=1,
          dropout_scenario="shutdown"), 1e-5),
], ids=["fedavg", "fedprox", "pods", "int8", "int8-fedprox",
        "sampled-uniform", "sampled-poisson-churn"])
def test_sharded_matches_dense(kw, rtol):
    """On a 1-device mesh the sharded engine is a pure re-layout of the
    dense scan — global params, per-round losses and the final state all
    agree (int8 at the quantization tolerance)."""
    job = _job(rounds=4, **kw)
    dense = job.run()
    shard = job.replace(shard_sites=True).run()
    _assert_trees_close(dense.global_params, shard.global_params,
                        rtol=rtol, atol=10 * rtol)
    assert shard.comm["sharded"] is True
    assert shard.comm["devices"] >= 1
    assert shard.comm["upload_bytes"] == dense.comm["upload_bytes"]
    # loss parity on participant rows: the dense engine also evaluates
    # (frozen) non-participants, the sharded engine never materializes
    # them (NaN rows) — so compare where the sharded engine trained
    for hd, hs in zip(dense.history, shard.history):
        assert hd["active"] == hs["active"]
        d = np.asarray(hd["per_site_loss"])
        s = np.asarray(hs["per_site_loss"])
        m = np.isfinite(s)
        assert m.sum() == hs["active"]
        np.testing.assert_allclose(d[m], s[m], rtol=1e-4)


def test_sharded_per_site_losses_match_dense():
    """Full participation: every site's loss trajectory is reproduced
    row for row, not just the round mean."""
    job = _job(rounds=3)
    dense = job.run()
    shard = job.replace(shard_sites=True).run()
    for hd, hs in zip(dense.history, shard.history):
        np.testing.assert_allclose(hd["per_site_loss"], hs["per_site_loss"],
                                   rtol=1e-4)


def test_sharded_state_live_after_donation():
    """The carry is donated into every compiled step; the returned state
    must be the live copy — readable, finite, and [S]-shaped — and a
    second identical run must reproduce it exactly (nothing aliased)."""
    job = _job(rounds=3, shard_sites=True)
    a = job.run()
    assert a.state is not None
    for leaf in jax.tree.leaves(a.state["params"]):
        arr = np.asarray(leaf)
        assert arr.shape[0] == 4 and np.isfinite(arr).all()
    b = job.run()
    _assert_trees_close(a.state["params"], b.state["params"], rtol=0)
    _assert_trees_close(a.global_params, b.global_params, rtol=0)


def test_sharded_records_participants_per_round():
    res = _job(rounds=3, shard_sites=True, sample="uniform:2",
               dropout_scenario="shutdown").run()
    for h in res.history:
        assert h["active"] == 2
        assert h["participants"] == 2
        assert h["k_cap"] >= 2


def test_sharded_unsupported_combos_raise():
    with pytest.raises(ValueError, match="shard"):
        _job(shard_sites=True, scheduler=BufferedScheduler(buffer_k=2)).run()
    with pytest.raises(ValueError, match="shard"):
        _job(shard_sites=True, strategy="gcml").run()
    with pytest.raises(ValueError, match="shard"):
        _job(shard_sites=True, compression="fp8").run()
    with pytest.raises(ValueError, match="shard"):
        _job(shard_sites=True, device_data=True).run()
    with pytest.raises(ValueError, match="shard"):
        _job(shard_sites=True, dp_clip=1.0, dp_noise_multiplier=1.0).run()
    with pytest.raises(ValueError, match="shard"):
        _job(shard_sites=True, transport="thread").run()
    # thinned participation without deterministic shutdown re-entry
    with pytest.raises(ValueError, match="shutdown"):
        _job(shard_sites=True, sample="uniform:2",
             dropout_scenario="disconnect").run()


# ---------------------------------------------------------------------------
# Host spans (repro.telemetry) and the per-round timing fields they give
# ---------------------------------------------------------------------------

SPAN_ENGINES = {"sync": dict(round_engine="scan"),
                "compressed": dict(round_engine="scan", compression="int8"),
                "buffered": dict(round_engine="scan",
                                 scheduler=BufferedScheduler(buffer_k=2)),
                "sharded": dict(shard_sites=True)}


def _spans_named(name):
    from repro import telemetry
    return [s for s in telemetry.spans() if s.name == name]


@pytest.mark.parametrize("engine", ["sync", "compressed", "buffered"])
def test_chunk_spans_on_every_scan_engine(engine, monkeypatch):
    """A 5-round job in chunks of 2 records one ``repro.chunk`` per
    chunk, one lower and one compile per chunk length, and the bytes of
    exactly the arrays handed to the chunk program."""
    from repro.core import round_engine
    handed = []
    orig = round_engine._ChunkRunner.run

    def run(self, kc, carry, xs):
        handed.append(sum(x.nbytes for x in jax.tree.leaves(xs)))
        return orig(self, kc, carry, xs)
    monkeypatch.setattr(round_engine._ChunkRunner, "run", run)
    res = _job(rounds=5, chunk_rounds=2, **SPAN_ENGINES[engine]).run()
    chunks = _spans_named("repro.chunk")
    assert [c.attrs["round"] for c in chunks] == [0, 2, 4]
    assert [c.attrs["rounds"] for c in chunks] == [2, 2, 1]
    ids = [c.id for c in chunks]
    for name in ("repro.chunk.inputs", "repro.chunk.execute",
                 "repro.chunk.record"):
        assert [s.parent for s in _spans_named(name)] == ids, name
    assert [s.attrs["bytes"] for s in
            _spans_named("repro.chunk.inputs")] == handed
    lower = _spans_named("repro.chunk.lower")
    compile_ = _spans_named("repro.chunk.compile")
    assert sorted(s.attrs["rounds"] for s in lower) == [1, 2]
    assert sorted(s.attrs["rounds"] for s in compile_) == [1, 2]
    assert res.compile_s == pytest.approx(
        sum(s.seconds for s in lower + compile_), rel=1e-9)


@pytest.mark.parametrize("engine", ["sync", "compressed", "buffered",
                                    "sharded"])
def test_wall_s_is_host_wall_time(engine):
    """``wall_s`` is a round's share of its chunk's host wall time (the
    ``repro.chunk`` span) less the lowering and compiling inside it, which
    ``compile_s`` holds; ``step_s`` is its share of the execute span."""
    kw = SPAN_ENGINES[engine]
    res = _job(rounds=5, chunk_rounds=None if "shard_sites" in kw else 2,
               **kw).run()
    chunks = _spans_named("repro.chunk")
    execs = _spans_named("repro.chunk.execute")
    assert len(res.history) == 5
    for h in res.history:
        assert h["wall_s"] >= h["step_s"] > 0.0
    assert sum(h["wall_s"] for h in res.history) + res.compile_s == (
        pytest.approx(sum(c.seconds for c in chunks), rel=1e-9))
    # the first chunk of each shape holds the compile, and its rounds'
    # wall_s does not
    assert res.compile_s > max(h["wall_s"] for h in res.history)
    assert sum(h["step_s"] for h in res.history) == pytest.approx(
        sum(e.seconds for e in execs), rel=1e-9)
    if "shard_sites" in kw:
        assert [c.attrs["rounds"] for c in chunks] == [1] * 5
        assert [s.attrs["rounds"]
                for s in _spans_named("repro.chunk.compile")] == [1]
