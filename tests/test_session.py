"""The unified FederatedJob API: transport parity (stacked ↔ TCP stack),
the sync/buffered scheduler seam, and the satellite fixes riding along
(stale-upload rejection, MeshConfig.for_sites)."""
import jax
import numpy as np
import pytest

from repro.api import (FederatedJob, StackedTransport, TaskConfig,
                       ThreadTransport, TcpTransport, resolve_transport)
from repro.comms.coordinator import AggregationServer
from repro.comms.peer import Peer
from repro.configs.base import MeshConfig
from repro.core.session import (BufferedScheduler, SyncScheduler,
                                availability_masks, resolve_scheduler)


def _token_job(**kw):
    base = dict(
        task=TaskConfig(kind="tokens", arch="smollm-135m", sites=4, batch=4,
                        seq=32, heterogeneity=0.3, seed=0),
        strategy="fedavg", rounds=3, lr=1e-3, seed=0)
    base.update(kw)
    return FederatedJob(**base)


def _assert_trees_close(a, b, rtol=2e-3, atol=1e-4):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Scheduler seam units
# ---------------------------------------------------------------------------


def test_sync_scheduler_barrier_semantics():
    s = SyncScheduler()
    assert s.discount(0) == 1.0
    assert s.discount(1) is None                 # straggler rejected
    assert s.discount(-1) is None
    assert not s.ready(3, 4)
    assert s.ready(4, 4)


def test_buffered_scheduler_k_of_s_trigger():
    b = BufferedScheduler(buffer_k=2)
    assert not b.ready(1, 4)
    assert b.ready(2, 4)                         # K of S
    assert b.ready(1, 1)                         # clamped to active count


def test_buffered_staleness_weights_sum_to_one_and_decrease():
    b = BufferedScheduler(buffer_k=2, alpha=0.5)
    w = b.staleness_weights([0, 1, 3])
    np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-6)
    assert w[0] > w[1] > w[2]                    # staler ⇒ lighter


def test_buffered_scheduler_rejects_too_stale():
    b = BufferedScheduler(buffer_k=2, max_staleness=2)
    assert b.discount(2) is not None
    assert b.discount(3) is None
    assert b.discount(-1) is None
    with pytest.raises(ValueError, match="staleness"):
        b.staleness_weights([0, 5])


def test_resolvers():
    assert isinstance(resolve_scheduler("sync"), SyncScheduler)
    assert isinstance(resolve_scheduler("buffered"), BufferedScheduler)
    assert isinstance(resolve_transport("stacked"), StackedTransport)
    assert isinstance(resolve_transport("thread"), ThreadTransport)
    assert isinstance(resolve_transport("tcp"), TcpTransport)
    with pytest.raises(KeyError):
        resolve_scheduler("bogus")
    with pytest.raises(KeyError):
        resolve_transport("bogus")


def test_availability_masks_deterministic():
    a = availability_masks(5, 2, seed=7, rounds=20)
    b = availability_masks(5, 2, seed=7, rounds=20)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (20, 5)
    assert (a.sum(axis=1) >= 3).all()            # never below S - N_max


# ---------------------------------------------------------------------------
# Aggregation-server scheduling (satellite: stale-upload rejection)
# ---------------------------------------------------------------------------


def test_server_rejects_stale_round_upload():
    """A straggler's round r−1 upload must NOT fold into round r."""
    agg = AggregationServer("127.0.0.1", 0, num_sites=2)
    p0, p1 = Peer(0), Peer(1)
    try:
        # server is collecting round 1; an upload tagged round 0 is stale
        ack = p0.upload(agg.addr, {"w": np.full(3, 99.0, np.float32)}, 0)
        assert ack["stale"] is True
        ack = p0.upload(agg.addr, {"w": np.full(3, 2.0, np.float32)}, 1)
        assert ack["stale"] is False
        p1.upload(agg.addr, {"w": np.full(3, 4.0, np.float32)}, 1)
        g = p0.download(agg.addr, 1)
        np.testing.assert_allclose(g["w"], 3.0, rtol=1e-6)   # 99.0 never folded
    finally:
        p0.close()
        p1.close()
        agg.stop()


def test_server_buffered_scheduler_aggregates_after_k():
    agg = AggregationServer("127.0.0.1", 0, num_sites=3,
                            scheduler=BufferedScheduler(buffer_k=2))
    peers = [Peer(i) for i in range(3)]
    try:
        peers[0].upload(agg.addr, {"w": np.full(2, 3.0, np.float32)}, 1)
        ack = peers[1].upload(agg.addr, {"w": np.full(2, 9.0, np.float32)}, 1)
        assert ack["round"] == 1                 # K=2 reached → new global
        g = peers[0].download(agg.addr, 1)
        np.testing.assert_allclose(g["w"], 6.0, rtol=1e-6)
        # the third (now stale-by-1) upload is admitted, discounted, and
        # starts the next buffer instead of being dropped
        ack = peers[2].upload(agg.addr, {"w": np.full(2, 1.0, np.float32)}, 1)
        assert ack["stale"] is False and ack["round"] == 1
        _, meta, _ = peers[0]._channel(agg.addr).request("status", {}, None)
        assert meta["pending"] == 1
    finally:
        for p in peers:
            p.close()
        agg.stop()


# ---------------------------------------------------------------------------
# Transport parity: same seed ⇒ same global model
# ---------------------------------------------------------------------------


def test_stacked_vs_tcp_stack_parity_fedavg():
    """Same seed ⇒ the vmapped simulator and the real TCP round trips
    (Peer/AggregationServer driven per site) agree after 3 FedAvg rounds."""
    stacked = _token_job().run()
    threaded = _token_job(transport="thread").run()
    assert threaded.transport == "thread"
    _assert_trees_close(stacked.global_params, threaded.global_params)
    np.testing.assert_allclose(stacked.losses, threaded.losses, rtol=1e-4)


def test_tcp_process_transport_parity():
    """One OS process per site over real TCP matches the simulator."""
    job = _token_job(
        task=TaskConfig(kind="tokens", arch="smollm-135m", sites=2, batch=2,
                        seq=16, seed=0),
        rounds=2)
    stacked = job.run()
    tcp = job.replace(transport="tcp").run()
    _assert_trees_close(stacked.global_params, tcp.global_params)


def test_tcp_transport_refuses_on_a_tpu_host(monkeypatch):
    """A TPU chip belongs to one process: with the chip held here, the
    tcp transport fails fast naming that rule instead of spawning site
    processes that would wait on the chip or land on the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one process at a time"):
        _token_job(transport="tcp").run()


def test_socket_transport_rejects_pooled():
    with pytest.raises(ValueError, match="pooled"):
        _token_job(strategy="pooled", transport="thread").run()


def test_buffered_over_tcp_stack_no_staleness_runaway():
    """Under a buffered scheduler the server finalizes ~S/K times per
    site round, so sites must anchor upload staleness to the global they
    last pulled — a loop-round tag would drift past max_staleness and
    get every later upload rejected (regression)."""
    # with loop-round tags, staleness grows ~(S/K − 1) per round: here it
    # passes max_staleness=6 around round 8 and every later upload from
    # every site is rejected (≥ 8 rejections by round 9, permanently);
    # with base-round anchoring it stays ≤ ~2 apart from rare thread-skew
    rounds, sites = 9, 4
    res = _token_job(
        task=TaskConfig(kind="tokens", arch="smollm-135m", sites=sites,
                        batch=2, seq=16, seed=0),
        rounds=rounds, transport="thread",
        scheduler=BufferedScheduler(buffer_k=2, max_staleness=6)).run()
    assert np.isfinite(res.losses).all()
    assert sum(res.history[-1]["stale_uploads"]) <= sites


def test_socket_transport_checkpoints_and_times_the_run(tmp_path):
    """--checkpoint must not be a silent no-op on socket transports (the
    final global is saved), and wall_s must span the actual run."""
    job = _token_job(
        task=TaskConfig(kind="tokens", arch="smollm-135m", sites=2, batch=2,
                        seq=16, seed=0),
        rounds=2, transport="thread", checkpoint_dir=str(tmp_path))
    res = job.run()
    assert res.wall_s > 0.5                      # not the post-hoc ~0 bug
    assert res.history[0]["wall_s"] > 0.1        # run-mean per round
    assert (tmp_path / "manifest.json").exists()
    assert list(tmp_path.glob("global_round*.npz"))


def test_coordinator_serves_lagging_round_assignment():
    """A site asking for round r must get round r's pairing even after a
    faster site already pulled round r+1 (regression: the coordinator
    used to overwrite its single stored assignment)."""
    from repro.comms.coordinator import CoordinationServer
    coord = CoordinationServer("127.0.0.1", 0, num_sites=3, seed=3)
    peers = [Peer(i) for i in range(3)]
    try:
        for p in peers:
            p.register(coord.addr)
        asg1 = peers[0].get_assignment(coord.addr, 1)
        asg2 = peers[0].get_assignment(coord.addr, 2)
        assert asg2["round"] == 2
        lagged = peers[1].get_assignment(coord.addr, 1)
        assert lagged["round"] == 1
        assert lagged["partner"] == asg1["partner"]
        assert lagged["is_receiver"] == asg1["is_receiver"]
    finally:
        for p in peers:
            p.close()
        coord.stop()


# ---------------------------------------------------------------------------
# Buffered-async end to end (stacked simulator)
# ---------------------------------------------------------------------------


def test_buffered_async_tracks_sync_fedavg():
    """FedBuff-style K<S buffered rounds land within 10% of sync FedAvg
    on the reduced token task (ROADMAP's async open item)."""
    rounds = 6
    sync = _token_job(rounds=rounds, lr=5e-3).run()
    buf = _token_job(rounds=rounds, lr=5e-3,
                     scheduler=BufferedScheduler(buffer_k=2)).run()
    assert buf.scheduler == "buffered"
    assert sync.final_loss < sync.losses[0]          # both actually train
    assert buf.final_loss < buf.losses[0]
    assert abs(buf.final_loss - sync.final_loss) <= 0.1 * sync.final_loss
    # versions advanced faster than rounds (K=2 of 4 ⇒ ~2 per round)
    assert buf.history[-1]["version"] >= rounds


def test_buffered_requires_fedavg():
    with pytest.raises(ValueError, match="fedavg"):
        _token_job(strategy="fedprox", scheduler="buffered").run()


# ---------------------------------------------------------------------------
# Satellites: MeshConfig.for_sites, job surface
# ---------------------------------------------------------------------------


def test_mesh_for_sites_hoists_fsdp_arithmetic():
    m = MeshConfig.for_sites(8)
    assert (m.sites_per_pod, m.fsdp, m.data_axis_size) == (8, 2, 16)
    m = MeshConfig.for_sites(16)
    assert (m.fsdp, m.data_axis_size) == (1, 16)
    m = MeshConfig.for_sites(3)                  # 16 % 3 != 0 → unsharded
    assert (m.fsdp, m.data_axis_size) == (1, 3)


def test_train_cli_has_quiet_not_verbose():
    from repro.launch.train import make_parser
    args = make_parser().parse_args([])
    assert args.quiet is False                   # progress on by default
    assert not hasattr(args, "verbose")          # old broken flag is gone
    assert make_parser().parse_args(["--quiet"]).quiet is True


def test_job_result_shape():
    res = _token_job(rounds=2).run()
    assert len(res.history) == 2
    assert {"round", "loss", "active", "per_site_loss", "wall_s"} <= \
        set(res.history[0])
    d = res.to_dict()
    assert np.isfinite(d["final_loss"])
    assert d["transport"] == "stacked" and d["scheduler"] == "sync"
