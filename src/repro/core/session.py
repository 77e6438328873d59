"""The sync/buffered-async scheduler seam + shared round bookkeeping.

A :class:`RoundScheduler` decides, for every upload the aggregation
point sees, (a) whether the upload is admitted and at what weight
(``discount``), and (b) when the buffered uploads are aggregated into a
new global model (``ready``).  The same two questions are asked by every
execution backend — the vmapped in-process simulator, the threaded TCP
stack, and the multi-process TCP stack — so one scheduler object plugs
into all of them:

  * :class:`SyncScheduler` — the classic barrier round: admit only
    uploads for the current round (anything else is acked ``stale`` and
    dropped), aggregate once every active site has reported.
  * :class:`BufferedScheduler` — FedBuff-style buffered async (Nguyen et
    al. 2022): aggregate after ``buffer_k`` of S uploads; admit late
    uploads at a staleness-discounted weight ``(1+τ)^(-alpha)``; reject
    uploads staler than ``max_staleness`` (the contributor resyncs to
    the current global instead).

Both fold straight into the PR-1 :class:`~repro.core.agg_engine.StreamingAccumulator`
— the accumulator normalizes by the folded weight total, so the
effective per-upload weights always sum to 1.

:class:`RoundRecorder` / :class:`JobResult` are the transport-agnostic
history + checkpoint bookkeeping every backend shares (``JobResult.comm``
carries the run's upload/download byte accounting — real wire bytes on
socket transports, simulated payload bytes on the stacked simulator),
and :func:`availability_masks` replays the Algorithm-2 dropout chain
deterministically so distributed site processes agree on the schedule
without extra coordination traffic.

Staleness interacts with the compression seam: a quantized *delta*
upload is anchored to the global version its ``discount`` staleness is
measured against, so the aggregation point keeps a bounded history of
recent globals to decode against (``AggregationServer.keep_globals``).
The full pull → local steps → upload → fold → broadcast lifecycle for
both schedulers is documented in ``docs/architecture.md``.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np


# ---------------------------------------------------------------------------
# Scheduler seam
# ---------------------------------------------------------------------------


class RoundScheduler:
    """When do buffered uploads become a new global model, and at what
    weight does each upload enter the buffer?"""

    name: str = "base"

    def discount(self, staleness: int) -> Optional[float]:
        """Weight multiplier for an upload ``staleness`` global-model
        versions old (0 = trained on the current global).  ``None``
        rejects the upload outright."""
        raise NotImplementedError

    def ready(self, buffered: int, expected: int) -> bool:
        """True once ``buffered`` folded uploads should be finalized into
        a new global (``expected`` = currently active sites)."""
        raise NotImplementedError


@dataclass
class SyncScheduler(RoundScheduler):
    """Barrier semantics: current-round uploads only, wait for all.

    ``round_deadline_s`` bounds how long the barrier waits on
    stragglers: once at least one upload has folded and the deadline
    has elapsed since it arrived, the aggregation server finalizes the
    round with whoever reported (graceful degradation — late uploads
    then hit the ordinary stale-ack path, reusing the Algorithm-2 mask
    machinery).  ``None`` keeps the strict barrier.  Wall-clock is a
    socket-transport concept; stacked engines ignore the deadline.
    """

    round_deadline_s: Optional[float] = None

    name = "sync"

    def discount(self, staleness: int) -> Optional[float]:
        return 1.0 if staleness == 0 else None

    def ready(self, buffered: int, expected: int) -> bool:
        return buffered >= expected


@dataclass
class BufferedScheduler(RoundScheduler):
    """FedBuff-style K-of-S buffered aggregation with staleness discount.

    ``buffer_k``      — aggregate once this many uploads are buffered
                        (clamped to the active-site count).
    ``alpha``         — staleness exponent: weight ∝ (1+τ)^(-alpha).
    ``max_staleness`` — uploads more than this many versions old are
                        rejected; their site resyncs without contributing.
    """

    buffer_k: int = 2
    alpha: float = 0.5
    max_staleness: int = 4

    name = "buffered"

    def discount(self, staleness: int) -> Optional[float]:
        if staleness < 0 or staleness > self.max_staleness:
            return None
        return float((1.0 + staleness) ** (-self.alpha))

    def ready(self, buffered: int, expected: int) -> bool:
        return buffered >= min(self.buffer_k, max(expected, 1))

    def staleness_weights(self, staleness: Sequence[int]) -> np.ndarray:
        """Normalized buffer weights for uploads at the given staleness
        values (what the accumulator's finalize-normalization produces)."""
        weights = []
        for tau in staleness:
            w = self.discount(int(tau))
            if w is None:
                raise ValueError(f"staleness {tau} outside "
                                 f"[0, {self.max_staleness}]")
            weights.append(w)
        d = np.asarray(weights, dtype=np.float64)
        return (d / d.sum()).astype(np.float32)


_SCHEDULERS = {"sync": SyncScheduler, "buffered": BufferedScheduler}


def resolve_scheduler(spec: Union[str, RoundScheduler, None]) -> RoundScheduler:
    if spec is None:
        return SyncScheduler()
    if isinstance(spec, RoundScheduler):
        return spec
    try:
        return _SCHEDULERS[spec]()
    except KeyError:
        raise KeyError(f"unknown scheduler {spec!r}; known: {sorted(_SCHEDULERS)}")


# ---------------------------------------------------------------------------
# Deterministic dropout replay (shared by job process and site workers)
# ---------------------------------------------------------------------------


def availability_masks(num_sites: int, max_dropout: int, seed: int,
                       rounds: int, topology=None,
                       pod_dropout: int = 0) -> np.ndarray:
    """[rounds, num_sites] bool active masks from the Algorithm-2 chain.

    Every participant that replays this with the same arguments gets the
    same schedule — distributed site processes agree on who is active
    each round without talking to the coordinator.

    With a pods :class:`~repro.core.topology.Topology` and
    ``pod_dropout > 0``, a second Algorithm-2 chain runs at the POD tier
    (an institution hub losing its uplink takes all member sites offline
    that round); the two chains consume distinct streams and compose by
    intersection.
    """
    from repro.core.dropout import SiteAvailability
    chain = SiteAvailability(num_sites, max_dropout, seed=seed)
    masks = np.stack([chain.step() for _ in range(rounds)])
    if topology is not None and pod_dropout:
        from repro.core.topology import pod_availability_masks
        pod_masks = pod_availability_masks(topology, num_sites, pod_dropout,
                                           seed, rounds)
        combined = masks & pod_masks
        # each chain on its own guarantees survivors (max_dropout < S,
        # pod_dropout < P); their intersection does not — an all-offline
        # round would deadlock sync barriers and zero the Eq. 1 weights.
        # Rule: pod-tier churn takes precedence on such rounds (the
        # active pods' sites participate).  Deterministic, so every
        # replaying participant agrees.
        empty = ~combined.any(axis=1)
        combined[empty] = pod_masks[empty]
        masks = combined
    return masks


# ---------------------------------------------------------------------------
# Round history / checkpoint bookkeeping (transport-agnostic)
# ---------------------------------------------------------------------------


@dataclass
class JobResult:
    """What ``FederatedJob.run`` hands back, whatever the backend."""

    history: List[Dict[str, Any]]
    global_params: Any                      # the aggregated global model
    wall_s: float
    transport: str
    scheduler: str
    state: Optional[Dict[str, Any]] = None  # stacked fl_state (stacked only)
    # communication accounting: upload/download bytes for the run (real
    # wire bytes on socket transports, simulated payload bytes on the
    # stacked simulator — see benchmarks/comm_bytes.py); None when the
    # strategy has no measured exchange
    comm: Optional[Dict[str, Any]] = None
    # seconds spent lowering and compiling each program shape (on the
    # stacked engines the sum of the ``repro.chunk.lower`` and
    # ``repro.chunk.compile`` spans), kept OUT of the per-round history:
    # there ``step_s`` is a round's share of device execution and
    # ``wall_s`` its share of the host's wall time less lowering and
    # compiling (``RoundRecorder.chunk``)
    compile_s: float = 0.0
    # crash resume: the checkpoint round this run re-entered from
    # (None = started at round 0); history then covers only the rounds
    # actually executed by this invocation
    resumed_from: Optional[int] = None
    # privacy report (repro.privacy): the run's (ε, δ) from the Rényi
    # accountant plus the DP-SGD / secure-aggregation settings; None
    # when no privacy mechanism is on
    privacy: Optional[Dict[str, Any]] = None
    # upload sanitation: how many uploads the aggregation point REJECTED
    # (non-finite leaves, norm outliers, undecodable payloads) instead
    # of folding into the global.  Server-authoritative on socket
    # transports; 0 on the stacked simulator, whose rows never cross a
    # wire.
    rejected_uploads: int = 0

    @property
    def losses(self) -> List[float]:
        return [h["loss"] for h in self.history]

    @property
    def final_loss(self) -> float:
        # empty history is legal: a resume that re-enters at the final
        # checkpoint has no rounds left to execute
        if not self.history:
            return float("nan")
        return self.history[-1]["loss"]

    def to_dict(self) -> Dict[str, Any]:
        return {"history": self.history, "final_loss": self.final_loss,
                "wall_s": self.wall_s, "compile_s": self.compile_s,
                "transport": self.transport,
                "scheduler": self.scheduler, "comm": self.comm,
                "resumed_from": self.resumed_from,
                "privacy": self.privacy,
                "rejected_uploads": self.rejected_uploads}


def check_engine_tag(meta: Dict[str, Any], engine: str):
    """Guard a ``driver_state`` resume: the checkpointed carry only fits
    the engine path that wrote it (scan carries ≠ loop state dicts)."""
    saved = meta.get("engine")
    if saved != engine:
        raise ValueError(
            f"driver_state checkpoint was written by engine {saved!r} but "
            f"this run resolves to {engine!r}; resume with the same "
            "round_engine / compression / scheduler settings")


def check_privacy_tag(meta: Dict[str, Any], dp_tag: Optional[List[Any]]):
    """Guard a resume across DP settings: the noise stream is a pure
    function of (seed, round, site, step) *given the DP config*, so
    re-entering with different clip/σ/mode would silently splice two
    different mechanisms into one trajectory (and void the accountant)."""
    saved = meta.get("dp")
    if saved is not None or dp_tag is not None:
        if list(saved or []) != list(dp_tag or []):
            raise ValueError(
                f"driver_state checkpoint was written with DP settings "
                f"{saved!r} but this run resolves to {dp_tag!r}; resume "
                "with the same dp_clip / dp_noise_multiplier / dp_mode "
                "/ seed")


class RoundRecorder:
    """Per-round history, progress printing and checkpointing — the
    bookkeeping every transport shares instead of reimplementing."""

    def __init__(self, rounds: int, *, verbose: bool = False,
                 log_every: Optional[int] = None,
                 checkpoint_dir: Optional[Union[str, Path]] = None,
                 ckpt_every: int = 10, num_sites: int = 1):
        self.rounds = rounds
        self.verbose = verbose
        self.log_every = log_every or max(rounds // 10, 1)
        self.ckpt_every = ckpt_every
        self.num_sites = num_sites
        self.history: List[Dict[str, Any]] = []
        self.store = None
        if checkpoint_dir:
            from repro.checkpoint import CheckpointStore
            self.store = CheckpointStore(Path(checkpoint_dir))
        self._t0 = time.time()
        self._t_last = self._t0
        self._chunk_start: Optional[int] = None

    @property
    def elapsed(self) -> float:
        """Seconds since the recorder (and hence the run) started."""
        return time.time() - self._t0

    def record(self, round_index: int, per_site_loss, active,
               global_fn=None, extra: Optional[Dict[str, Any]] = None):
        now = time.time()
        per_site = np.asarray(per_site_loss, dtype=np.float64).reshape(-1)
        loss = float(np.nanmean(per_site))
        n_active = int(np.sum(active))
        rec = {"round": round_index, "loss": loss, "active": n_active,
               "per_site_loss": per_site.tolist()}
        if self._chunk_start is None:     # else RoundRecorder.chunk's share
            rec["wall_s"] = now - self._t_last
        rec.update(extra or {})
        self.history.append(rec)
        self._t_last = now
        if self.verbose and (round_index % self.log_every == 0
                             or round_index == self.rounds - 1):
            print(f"round {round_index:4d} loss {loss:.4f} "
                  f"active {n_active}/{self.num_sites}")
        if (self.store and global_fn is not None
                and round_index % self.ckpt_every == 0):
            self.store.save("global", round_index, global_fn())

    @contextlib.contextmanager
    def chunk(self, first_round: int, rounds: int):
        """One host iteration of a stacked engine, whose ``rounds`` rounds
        run as one program and are recorded inside the block: the
        ``repro.chunk`` span.  When it closes, each round recorded inside
        takes an equal share of the span's wall time less the lowering
        and compiling inside it (those go to ``compile_s``)."""
        from repro import telemetry
        self._chunk_start = len(self.history)
        try:
            with telemetry.span("repro.chunk", round=first_round,
                                rounds=rounds) as span:
                yield span
        finally:
            wall = span.seconds - span.inner("repro.chunk.lower",
                                             "repro.chunk.compile")
            for rec in self.history[self._chunk_start:]:
                rec["wall_s"] = wall / rounds
            self._chunk_start = None
            self._t_last = time.time()

    def save_state(self, round_index: int, state_fn,
                   meta: Optional[Dict[str, Any]] = None):
        """Persist resumable engine state ("driver_state" tag) on the
        same ``ckpt_every`` grid as the global model.  ``state_fn`` is
        called lazily (host transfer of a scan carry is not free) and
        must return a pytree whose structure the resuming engine can
        rebuild as a ``like`` — the ``meta["engine"]`` tag guards
        against resuming across engine paths with different carries."""
        if self.store and round_index % self.ckpt_every == 0:
            self.store.save("driver_state", round_index, state_fn(),
                            meta=meta)

    def result(self, global_params, *, transport: str, scheduler: str,
               state=None, comm=None, compile_s: float = 0.0,
               resumed_from: Optional[int] = None,
               privacy: Optional[Dict[str, Any]] = None,
               rejected_uploads: int = 0) -> JobResult:
        return JobResult(history=self.history, global_params=global_params,
                         wall_s=time.time() - self._t0, transport=transport,
                         scheduler=scheduler, state=state, comm=comm,
                         compile_s=compile_s, resumed_from=resumed_from,
                         privacy=privacy, rejected_uploads=rejected_uploads)
