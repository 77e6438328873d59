"""One ``FederatedJob`` API — the paper's unified communication stack.

The headline FedKBP+ claim is that participants run the *same* FL
scripts whether colocated on one workstation or spread across machines.
This module is that surface: a declarative job object that owns task
construction (tokens/dose/seg), strategy, dropout schedule,
checkpointing and metrics, and executes rounds through a pluggable
:class:`Transport`:

  * :class:`StackedTransport` — the vmapped/jitted single-process
    simulator (fast default; every strategy incl. GCML gossip).
  * :class:`ThreadTransport`  — every site is a real ``Peer`` with its
    own server socket, driven by an in-process thread; rounds go through
    ``AggregationServer`` / ``CoordinationServer`` over real TCP.
  * :class:`TcpTransport`     — same wire protocol, but each site is its
    own OS process (the paper's deployment shape).

Three more seams sit on top of the transport seam:

  * the **topology seam** (:mod:`repro.core.topology`):
    ``topology="pods:K"`` turns the flat star into a two-tier pod
    federation — per-pod partial aggregation, then a cross-pod combine.
    On the stacked simulator that is a segment-reduce by pod id inside
    the compiled round (``AggregationEngine.aggregate_pods``); on the
    socket transports it is a real server hierarchy
    (:mod:`repro.comms.pods`): one ``AggregationServer`` per pod plus a
    root combiner that pod-leader relays re-upload partials to, with
    ``result.comm`` splitting intra-pod vs cross-pod wire bytes.
    ``pod_dropout=N`` churns whole pods (Algorithm 2 at the pod tier);
  * the **scheduler seam** (:mod:`repro.core.session`): ``SyncScheduler``
    keeps barrier rounds, ``BufferedScheduler`` gives FedBuff-style
    buffered-async aggregation — on the stacked simulator *and* on the
    TCP server, since both fold uploads through the same
    ``StreamingAccumulator``; under a pods topology the choice applies
    *per tier* (``Topology(intra_scheduler=…, inter_scheduler=…)``);
  * the **compression seam** (:mod:`repro.comms.compression`):
    ``compression="int8" | "fp8" | "topk-sparse"`` quantizes each site's
    upload as a per-chunk-scaled delta against the global it last
    pulled, with a client-side error-feedback residual carried across
    rounds; payloads decode in ``AggregationServer._handle("upload")``
    (and at gossip receivers) before the accumulator fold, so one codec
    implementation serves all three transports at once.

    job = FederatedJob(task=TaskConfig(kind="tokens", arch="qwen3-8b",
                                       sites=4, heterogeneity=0.5),
                       strategy="fedavg", rounds=12)
    result = job.run()                        # local, one process
    result = job.replace(transport="tcp").run()   # real multi-process TCP
    result = job.replace(compression="int8").run()  # ~4x smaller uploads

``job.run(rounds)`` is the only round loop in the codebase — examples,
the train CLI and the benchmarks all drive it; ``result.comm`` reports
the run's upload/download byte volume (real wire bytes on the socket
transports, simulated payload bytes on the stacked simulator).

The per-round lifecycle (pull → local steps → upload → fold →
broadcast), the stale-upload rejection and staleness-discount rules,
and how the seams compose are documented in ``docs/architecture.md``.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.comms.compression import (KEEP_GLOBALS_DEFAULT, Codec,
                                     DownlinkCompressor, UploadCompressor,
                                     decode_download, decode_upload,
                                     resolve_codec, tree_payload_nbytes)
from repro.comms.transport import WireConfig
from repro.configs.base import FederationConfig, MeshConfig
from repro.core import federation as F
from repro.core import stacking
from repro.core.adversary import parse_adversary
from repro.core.agg_engine import (StreamingAccumulator, parse_aggregator,
                                   per_site_nbytes)
from repro.core.sampling import (ClientSampler, compose_participation,
                                 resolve_sampler)
from repro.core.session import (BufferedScheduler, JobResult, RoundRecorder,
                                RoundScheduler, SyncScheduler,
                                availability_masks, check_engine_tag,
                                check_privacy_tag, resolve_scheduler)
from repro.core.strategies import base as strat_base
from repro.core.topology import FLAT, Topology, resolve_topology
from repro.optim import adamw


# ---------------------------------------------------------------------------
# Task construction (tokens / dose / seg) — declarative and picklable, so
# TcpTransport site processes can rebuild the exact task from the job alone.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TaskConfig:
    """What the federation trains on.  ``kind`` ∈ {tokens, dose, seg}."""

    kind: str = "tokens"
    sites: int = 4
    batch: int = 4                      # per-site batch per local step
    heterogeneity: float = 0.0          # non-IID knob (0 = IID)
    seed: int = 0                       # data seed (independent of job seed)
    # -- tokens ------------------------------------------------------------
    arch: str = "smollm-135m"
    reduced: bool = True
    seq: int = 64
    # -- volumetric (dose / seg) -------------------------------------------
    volume: Tuple[int, int, int] = (16, 16, 16)
    num_oars: int = 2                   # dose: OAR channels
    in_channels: int = 2                # seg: input channels
    num_classes: int = 3                # seg: label classes
    base_filters: int = 8
    num_levels: int = 2
    site_pools: Optional[Tuple[int, ...]] = None   # per-site distinct cases

    def model_config(self):
        """The model config this task trains (ModelConfig or SANetConfig)."""
        from repro.models.sanet import SANetConfig
        if self.kind == "tokens":
            from repro.configs.registry import get_arch
            arch = get_arch(self.arch)
            return arch.reduced() if self.reduced else arch.CONFIG
        if self.kind == "dose":
            return SANetConfig(in_channels=2 + self.num_oars, out_channels=1,
                               base_filters=self.base_filters,
                               num_levels=self.num_levels, task="dose")
        if self.kind == "seg":
            return SANetConfig(in_channels=self.in_channels,
                               out_channels=self.num_classes,
                               base_filters=self.base_filters,
                               num_levels=self.num_levels, task="segmentation")
        raise ValueError(f"unknown task kind {self.kind!r}")

    def build(self) -> "TaskBundle":
        if self.kind == "tokens":
            return _build_token_task(self)
        if self.kind in ("dose", "seg"):
            return _build_volume_task(self)
        raise ValueError(f"unknown task kind {self.kind!r}")


@dataclass
class TaskBundle:
    """Built task: loss/init fns + batch samplers over the generator."""

    task: TaskConfig
    loss_fn: Callable
    logits_fn: Optional[Callable]
    init_fn: Callable
    model_cfg: Any
    sample: Callable[[int, int], Dict[str, np.ndarray]]   # (site, step) -> [B,…]
    stacked: Callable[[int, int], Dict[str, np.ndarray]]  # (round, K) -> [S,K,B,…]
    # traced (key, K, B) -> [S,K,B,…] batch sampler for the compiled
    # round engine's on-device data path (token AND dose/seg tasks);
    # None when no traced generator applies (site_pools case recycling
    # is host-only)
    traced_stacked: Optional[Callable] = None

    @staticmethod
    def pooled_view(b: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Concatenate the site axis into one site's batch
        ([S, K, B, …] → [1, K, S·B, …]) — the paper's Pooled upper
        baseline.  The ONE definition of the pooled layout, shared by
        the per-round loop and the scan engine's chunk builder."""
        return {k: np.reshape(np.swapaxes(x, 0, 1),
                              (1, x.shape[1], -1) + x.shape[3:])
                for k, x in b.items()}

    def round_batches(self, round_index: int, local_steps: int,
                      pooled: bool = False):
        """[S, K, B, …] batches for one round (K = local steps); with
        ``pooled``, the :meth:`pooled_view` of them."""
        b = self.stacked(round_index, local_steps)
        if pooled:
            b = self.pooled_view(b)
        return jax.tree.map(jnp.asarray, b)

    def site_batches(self, site: int, round_index: int, local_steps: int):
        """[1, K, B, …] — one site's slice of :meth:`round_batches`.  The
        sample indexing (``round·K + k``) must mirror the generators'
        ``stacked_batches``; transport parity depends on it, and
        regenerating only this site's data keeps workers O(B) instead of
        O(S·B) per round."""
        ks = [self.sample(site, round_index * local_steps + k)
              for k in range(local_steps)]
        b = {k: np.stack([x[k] for x in ks])[None] for k in ks[0]}
        return jax.tree.map(jnp.asarray, b)


def _build_token_task(task: TaskConfig) -> TaskBundle:
    from repro.data.synthetic import TokenTaskGenerator
    from repro.models import transformer as T
    cfg = task.model_config()
    gen = TokenTaskGenerator(vocab_size=cfg.vocab_size, num_sites=task.sites,
                             heterogeneity=task.heterogeneity,
                             num_codebooks=cfg.num_codebooks, seed=task.seed)

    def logits_fn(params, batch):
        logits, _ = T.forward(params, batch["tokens"], cfg)
        return logits[:, :-1], batch["tokens"][:, 1:]

    return TaskBundle(
        task=task,
        loss_fn=lambda p, b: T.next_token_loss(p, b, cfg),
        logits_fn=logits_fn,
        init_fn=lambda k: T.init(k, cfg),
        model_cfg=cfg,
        sample=lambda site, step: {
            "tokens": gen.sample(site, step, task.batch, task.seq)},
        stacked=lambda rnd, k: gen.stacked_batches(rnd, k, task.batch,
                                                   task.seq),
        traced_stacked=lambda key, k, b: gen.traced_stacked_batches(
            key, k, b, task.seq))


def _build_volume_task(task: TaskConfig) -> TaskBundle:
    from repro.data.synthetic import DoseTaskGenerator, SegTaskGenerator
    from repro.models import sanet as sanet_mod
    scfg = task.model_config()
    if task.kind == "dose":
        gen = DoseTaskGenerator(volume=task.volume, num_oars=task.num_oars,
                                num_sites=task.sites,
                                heterogeneity=task.heterogeneity,
                                seed=task.seed, site_pools=task.site_pools)
        loss_fn = lambda p, b: sanet_mod.dose_loss(p, b, scfg)

        def logits_fn(params, batch):
            pred, _ = sanet_mod.sanet_apply(params, batch["volume"], scfg)
            # dose regression viewed as binary high/low for DCML regions
            logits = jnp.concatenate([pred, -pred], axis=-1)
            labels = (batch["dose"][..., 0] > 0.5).astype(jnp.int32)
            return logits, labels
    else:
        gen = SegTaskGenerator(volume=task.volume, in_channels=task.in_channels,
                               num_classes=task.num_classes,
                               num_sites=task.sites,
                               heterogeneity=task.heterogeneity,
                               seed=task.seed, site_pools=task.site_pools)
        loss_fn = lambda p, b: sanet_mod.segmentation_loss(p, b, scfg)

        def logits_fn(params, batch):
            pred, _ = sanet_mod.sanet_apply(params, batch["volume"], scfg)
            return pred, batch["labels"]

    return TaskBundle(
        task=task, loss_fn=loss_fn, logits_fn=logits_fn,
        init_fn=lambda k: sanet_mod.sanet_init(k, scfg), model_cfg=scfg,
        sample=lambda site, step: gen.sample(site, step, task.batch),
        stacked=lambda rnd, k: gen.stacked_batches(rnd, k, task.batch),
        # jnp generator: device_data=True covers the SA-Net tasks too;
        # site_pools recycling indexes by host step, so it stays host-side
        traced_stacked=(gen.traced_stacked_batches
                        if task.site_pools is None else None))


# ---------------------------------------------------------------------------
# The job
# ---------------------------------------------------------------------------


@dataclass
class FederatedJob:
    """A fully-specified federated run; ``run()`` executes it through the
    configured transport and scheduler.  Declarative and picklable — the
    TCP transport ships the job itself to every site process."""

    task: TaskConfig = field(default_factory=TaskConfig)
    strategy: str = "fedavg"
    rounds: int = 10
    local_steps: int = 1
    # optimizer / strategy hyper-parameters
    lr: float = 1e-3
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    prox_mu: float = 0.01
    gcml_lambda: float = 0.5
    gcml_contrast_beta: float = 1.0
    dcml_lr: Optional[float] = None     # default: lr
    # Algorithm-2 dropout schedule
    max_dropout: int = 0
    dropout_scenario: str = "disconnect"
    case_counts: Optional[Tuple[int, ...]] = None   # Eq. 1 m_i (None=uniform)
    # cross-device client sampling (repro.core.sampling): "none" |
    # "uniform:K" | "poisson:q" — which sites are *scheduled* each round,
    # intersected with the Algorithm-2 availability masks; Eq. 1 weights
    # are 1/π inclusion-probability reweighted (Horvitz–Thompson) so the
    # sampled aggregate is unbiased for the dense one
    sample: Union[str, "ClientSampler"] = "none"
    # execution
    transport: Union[str, "Transport"] = "stacked"
    scheduler: Union[str, RoundScheduler] = "sync"
    # federation topology: "flat" (one star) or "pods:K" / a Topology —
    # two tiers of aggregation (per-pod partials → cross-pod combine)
    # honored by every transport; see repro.core.topology
    topology: Union[str, Topology] = "flat"
    pod_dropout: int = 0                # Algorithm-2 churn at the pod tier
    compression: Union[str, Codec] = "none"   # upload codec (comms seam)
    error_feedback: bool = True         # carry quantization residual
    # download codec: the server keeps per-site error-feedback residual
    # references and broadcasts each install as a quantized delta against
    # that site's last-acknowledged global (dense bootstrap for new or
    # evicted references — same rejoin rule as uploads).  fedavg/fedprox
    # sync rounds only; secure_agg downloads stay dense (the masked sum
    # is the only thing the server may materialize).
    down_compression: Union[str, Codec] = "none"
    # privacy tier (repro.privacy).  DP-SGD is ON iff dp_clip > 0:
    # per-site/per-example gradient clipping + Gaussian noise inside
    # every site update (all transports, compiled into the scan engine),
    # with the Rényi accountant's (ε, δ) on ``result.privacy``.
    # secure_agg=True masks uploads pairwise in fixed-point int64 so the
    # aggregation point only ever sees their sum (socket transports,
    # sync schedulers, compression="none"; dropped/lease-expired sites
    # are repaired by seed recovery).
    dp_clip: float = 0.0
    dp_noise_multiplier: float = 0.0
    dp_delta: float = 1e-5
    dp_mode: str = "per-site"           # clipping unit: per-site | per-example
    secure_agg: bool = False
    # Byzantine-robustness tier (repro.core.adversary + the robust
    # combine seam on the aggregation engine).  ``aggregator`` selects
    # the site→global rule applied to the round's active uploads:
    # "fedavg" (Eq. 1 weighted mean) | "trimmed:f" (coordinate-wise
    # trimmed mean, f per side) | "median" | "krum:f" (pick the upload
    # with the smallest distance score) | "normclip:c" (per-upload L2
    # clip to c before the weighted mean).  ``adversary`` injects a
    # deterministic fault plan — "sign_flip:f" | "label_flip:f" |
    # "scale:c:f" | "noise:s:f" — where f seeded sites perturb what they
    # expose to aggregation, bit-identically on the stacked engines and
    # the socket workers.  ``round_deadline_s`` bounds the socket
    # transports' sync barrier (after the deadline the round proceeds
    # with whoever folded; stragglers are acked stale).
    # ``max_upload_norm`` rejects norm-outlier uploads at the server
    # with a typed ack (non-finite uploads are always rejected).
    aggregator: str = "fedavg"
    adversary: Optional[str] = None
    round_deadline_s: Optional[float] = None
    max_upload_norm: Optional[float] = None
    seed: int = 0                       # init + dropout + pairing seed
    io_timeout: float = 120.0           # socket-transport exchange bound
    # deployable wire (socket transports): hello auth secret, optional
    # TLS, chunked streaming threshold, retry/backoff, fault injection —
    # see repro.comms.transport.WireConfig
    wire: WireConfig = field(default_factory=WireConfig)
    # elastic membership (socket transports): sites lease their seat and
    # renew by heartbeat; a site silent for lease_ttl seconds is expired
    # into the round's Algorithm-2 dropout accounting instead of
    # deadlocking the barrier.  None = fixed roster (the paper's setup).
    lease_ttl: Optional[float] = None
    # stacked-transport round engine (repro.core.round_engine): "auto"
    # compiles chunks of rounds into one donated lax.scan and falls back
    # to the per-round loop where the scan can't replicate semantics;
    # "scan" insists (raises on unsupported combos); "loop" forces the
    # retired per-round driver (the parity oracle)
    round_engine: str = "auto"
    chunk_rounds: Optional[int] = None  # rounds per compiled chunk (None=auto)
    device_data: bool = False           # generate batches on-device (tokens)
    # stacked transport: partition the [S, N] engine buffer and the
    # vmapped site-update axis across a device mesh (shard_map), and
    # materialize only the sampled rows per round (gather-by-index into
    # a [K, N] working buffer) — the cross-device engine that lets a
    # 10,000-site job at 1% sampling run on one box
    shard_sites: bool = False
    # bookkeeping
    checkpoint_dir: Optional[str] = None
    ckpt_every: int = 10
    verbose: bool = False
    log_every: Optional[int] = None

    # -- derived -----------------------------------------------------------

    @property
    def train_sites(self) -> int:
        """Sites in the *training* federation (Pooled trains as 1 site
        over the concatenated data)."""
        return 1 if self.strategy == "pooled" else self.task.sites

    @property
    def topo(self) -> Topology:
        return resolve_topology(self.topology)

    @property
    def dp(self):
        """The job's :class:`~repro.privacy.DPConfig`, or None (off)."""
        if self.dp_clip <= 0 and self.dp_noise_multiplier <= 0:
            return None
        from repro.privacy import DPConfig
        return DPConfig(clip=self.dp_clip,
                        noise_multiplier=self.dp_noise_multiplier,
                        delta=self.dp_delta, mode=self.dp_mode,
                        seed=self.seed)

    def dp_tag(self) -> Optional[List[Any]]:
        """Checkpoint-meta fingerprint of the DP settings — a resume
        with a different mechanism must refuse, not splice streams."""
        dp = self.dp
        if dp is None:
            return None
        return [dp.clip, dp.noise_multiplier, dp.mode, dp.seed]

    @property
    def mask_secret(self) -> str:
        """The shared secret the pairwise mask seeds derive from: the
        wire auth secret when set (the deployed configuration), else a
        seed-derived default so offline tests run without one."""
        return self.wire.secret or f"fedkbp-mask:{self.seed}"

    def privacy_report(self, rounds: Optional[int] = None
                       ) -> Optional[Dict[str, Any]]:
        """``JobResult.privacy``: accountant output + mechanism settings
        (None when no privacy mechanism is on).  ε accounts the FULL
        logical run of ``rounds`` — a crash-resumed invocation replays
        the same noise stream, it does not spend new budget."""
        dp = self.dp
        if dp is None and not self.secure_agg:
            return None
        rep: Dict[str, Any] = {"secure_agg": bool(self.secure_agg)}
        if dp is None:
            rep["mechanism"] = "none"
            return rep
        from repro.privacy import gaussian_epsilon
        steps = (self.rounds if rounds is None else rounds) * self.local_steps
        rep.update({
            "mechanism": "dp-sgd", "mode": dp.mode, "clip": dp.clip,
            "noise_multiplier": dp.noise_multiplier, "delta": dp.delta,
            "steps": steps, "accountant": "rdp-gaussian",
            "epsilon": gaussian_epsilon(dp.noise_multiplier, steps,
                                        dp.delta)})
        # privacy amplification by subsampling: under poisson:q client
        # sampling each site's round contribution is released only with
        # probability q, so the accountant composes the subsampled
        # Gaussian mechanism instead (ε_sub ≤ ε).  uniform:K is sampling
        # WITHOUT replacement — the Poisson amplification theorem does
        # not cover it, so it conservatively keeps the unsampled ε.
        sampler = self.sampler
        if sampler.kind == "poisson" and self.sampled:
            q = sampler.inclusion_probability(self.task.sites)
            rep.update({
                "sampling_rate": q, "accountant": "rdp-sgm-poisson",
                "epsilon": gaussian_epsilon(dp.noise_multiplier, steps,
                                            dp.delta, sampling_rate=q)})
        return rep

    def replace(self, **kw) -> "FederatedJob":
        return dataclasses.replace(self, **kw)

    @property
    def sampler(self) -> ClientSampler:
        """The job's resolved :class:`~repro.core.sampling.ClientSampler`."""
        return resolve_sampler(self.sample)

    @property
    def aggregator_spec(self):
        """The job's parsed :class:`~repro.core.agg_engine.AggregatorSpec`."""
        return parse_aggregator(self.aggregator)

    @property
    def adversary_plan(self):
        """The job's parsed :class:`~repro.core.adversary.AdversaryPlan`,
        or None when every site is honest."""
        return parse_adversary(self.adversary, seed=self.seed)

    @property
    def sampled(self) -> bool:
        """True when client sampling actually thins participation
        (``uniform:S`` and ``poisson:1.0`` are the dense run)."""
        return not self.sampler.is_trivial(self.task.sites)

    def participation(self, rounds: int) -> Tuple[np.ndarray, np.ndarray]:
        """``(participate, scale)`` for the run: the [rounds, S] bool
        participation schedule (sampled ∩ available, with the
        deterministic availability-precedence rule on empty rounds) and
        the [rounds, S] float32 Horvitz–Thompson ``1/π`` weight scale.
        Pure function of the job config, so every transport, engine and
        distributed worker replays one schedule."""
        if self.pod_dropout and not self.topo.is_pods:
            raise ValueError("pod_dropout requires a pods topology "
                             "(--topology pods:K)")
        if self.sampled and self.strategy == "pooled":
            raise ValueError("client sampling is meaningless for the "
                             "pooled centralized baseline; use sample="
                             "'none'")
        avail = availability_masks(self.task.sites, self.max_dropout,
                                   self.seed, rounds, topology=self.topo,
                                   pod_dropout=self.pod_dropout)
        return compose_participation(self.sampler, avail, self.seed)

    def masks(self, rounds: int) -> np.ndarray:
        """The run's [rounds, S] participation schedule — Algorithm-2
        availability (site-tier churn composed with ``pod_dropout``
        pod-tier churn) intersected with the client-sampling schedule.
        THE mask source for every transport, so distributed workers and
        the driver replay one schedule.  Without sampling this is the
        availability schedule verbatim."""
        return self.participation(rounds)[0]

    def weight_scale(self, rounds: int) -> np.ndarray:
        """[rounds, S] float32 Eq. 1 inclusion-probability factors
        (``1/π`` on sampled rows, ``1.0`` on fallback rounds); the
        engines multiply this into ``normalized_weights`` only when
        :attr:`sampled` is True, keeping unsampled runs bit-identical."""
        return self.participation(rounds)[1]

    def tier_schedulers(self) -> Tuple[RoundScheduler, RoundScheduler]:
        """(intra-pod, cross-pod) schedulers: the topology's per-tier
        overrides, falling back to the job's scheduler at both tiers."""
        topo = self.topo
        return (resolve_scheduler(topo.intra_scheduler
                                  if topo.intra_scheduler is not None
                                  else self.scheduler),
                resolve_scheduler(topo.inter_scheduler
                                  if topo.inter_scheduler is not None
                                  else self.scheduler))

    def federation(self, num_sites: Optional[int] = None,
                   strategy: Optional[str] = None) -> FederationConfig:
        sites = self.train_sites if num_sites is None else num_sites
        counts = self.case_counts
        if counts is not None and len(counts) != sites:
            counts = None               # e.g. a 1-site worker view
        return FederationConfig(
            num_sites=sites, strategy=strategy or self.strategy,
            local_steps=self.local_steps, rounds=self.rounds,
            prox_mu=self.prox_mu, gcml_lambda=self.gcml_lambda,
            gcml_contrast_beta=self.gcml_contrast_beta,
            max_dropout_sites=self.max_dropout,
            dropout_scenario=self.dropout_scenario,
            site_case_counts=counts)

    def context(self, bundle: Optional[TaskBundle] = None,
                num_sites: Optional[int] = None,
                strategy: Optional[str] = None,
                dp_site_base: int = 0) -> F.FLContext:
        """The FLContext view of this job (stacked or per-site worker).
        The topology rides along only on the full-federation view — a
        worker's 1-site (or otherwise resized) context is flat, since
        tiering happens at its aggregation point, not inside its rounds.
        ``dp_site_base`` maps the view's site rows to global site ids so
        a socket worker draws the same DP noise as its stacked twin."""
        bundle = bundle or self.task.build()
        fed = self.federation(num_sites, strategy)
        topo = self.topo if num_sites is None and self.strategy != "pooled" \
            else FLAT
        return F.FLContext(
            fed=fed, mesh=MeshConfig.for_sites(fed.num_sites),
            case_weights=jnp.asarray(fed.case_weights()),
            loss_fn=bundle.loss_fn, logits_fn=bundle.logits_fn,
            optimizer=adamw(self.lr, weight_decay=self.weight_decay),
            grad_clip=self.grad_clip, dcml_lr=self.dcml_lr or self.lr,
            topology=topo, privacy=self.dp, dp_site_base=dp_site_base,
            aggregator=self.aggregator_spec,
            # in-round fault injection runs only on the full-federation
            # stacked view; a worker's 1-site (or local-strategy resized)
            # context stays honest — socket workers perturb their wire
            # payload host-side at the same seam instead
            adversary=(self.adversary_plan
                       if num_sites is None and strategy is None else None))

    def recorder(self, rounds: int, num_sites: int) -> RoundRecorder:
        return RoundRecorder(rounds, verbose=self.verbose,
                             log_every=self.log_every,
                             checkpoint_dir=self.checkpoint_dir,
                             ckpt_every=self.ckpt_every, num_sites=num_sites)

    def run(self, rounds: Optional[int] = None,
            resume: bool = False) -> JobResult:
        """Execute the federation — the one round loop.

        ``resume=True`` re-enters a killed/crashed job from the newest
        usable checkpoint under ``checkpoint_dir`` instead of round 0:
        the stacked engines reload their full carry (fl_state + engine
        buffers + EF residuals), the socket transports reload the driver
        global and every site's own state at the newest round all of
        them share.  With nothing on disk the run starts fresh
        (``result.resumed_from`` is None).  At checkpoint-aligned
        boundaries the resumed loss trajectory is identical to an
        uninterrupted run.  The run's host spans are read back with
        ``repro.telemetry.spans()``."""
        telemetry.start_run()
        return resolve_transport(self.transport).execute(
            self, self.rounds if rounds is None else rounds, resume=resume)


# ---------------------------------------------------------------------------
# Transports
# ---------------------------------------------------------------------------


class Transport:
    """Execution backend protocol: run ``rounds`` FL rounds of ``job``
    (optionally re-entering from the job's checkpoints)."""

    name = "base"

    def execute(self, job: FederatedJob, rounds: int,
                resume: bool = False) -> JobResult:
        raise NotImplementedError


def _driver_resume_round(job: FederatedJob, resume: bool) -> Optional[int]:
    """Stacked transport: the newest ``driver_state`` checkpoint round,
    or None for a fresh start.  ``resume=True`` without a
    ``checkpoint_dir`` has nothing to resume from and raises."""
    if not resume:
        return None
    if not job.checkpoint_dir:
        raise ValueError("run(resume=True) needs checkpoint_dir set")
    from repro.checkpoint import CheckpointStore
    saved = CheckpointStore(Path(job.checkpoint_dir)).saved_rounds(
        "driver_state")
    return saved[-1] if saved else None


def _socket_resume_point(job: FederatedJob, num_sites: int):
    """Socket transports: ``(resume_round, global)`` — the newest round
    present in the driver's "global" store AND every site's own
    sub-store, i.e. the round every participant can re-enter from.
    ``(None, None)`` when no common round survived (fresh start)."""
    from repro.checkpoint import CheckpointStore
    store = CheckpointStore(Path(job.checkpoint_dir))
    common = set(store.saved_rounds("global"))
    for i in range(num_sites):
        sub = CheckpointStore(Path(job.checkpoint_dir) / f"site{i}")
        common &= set(sub.saved_rounds("state"))
    if not common:
        return None, None
    rr = max(common)
    like = job.task.build().init_fn(jax.random.PRNGKey(job.seed))
    g, _ = store.load("global", rr, like)
    return rr, g


def _socket_down_refs(job: FederatedJob, rr: int, num_sites: int):
    """Per-site downlink references the aggregation server persisted at
    resume round ``rr`` (tags ``downref{sid}``) → the ``initial_down``
    map a restarted server seeds its :class:`DownlinkCompressor` from.
    Sites without a saved reference simply re-enter through a dense
    bootstrap — resume never deadlocks on a missing tag."""
    from repro.checkpoint import CheckpointStore
    store = CheckpointStore(Path(job.checkpoint_dir))
    like = job.task.build().init_fn(jax.random.PRNGKey(job.seed))
    out = {}
    for sid in range(num_sites):
        tag = f"downref{sid}"
        if rr in set(store.saved_rounds(tag)):
            held, meta = store.load(tag, rr, like)
            out[sid] = (held, int(meta["held_round"]))
    return out or None


def _validate_robustness(job: FederatedJob) -> None:
    """Fail-loud composition guards for the robustness seams, shared by
    every transport.  Robust rules need to SEE the round's individual
    plaintext uploads side by side; compositions that hide, quantize or
    stream them away are typed errors, never silent downgrades."""
    spec = job.aggregator_spec          # raises on a malformed spec string
    plan = job.adversary_plan           # raises on a malformed plan string
    if (not spec.robust and plan is None and job.max_upload_norm is None
            and job.round_deadline_s is None):
        return
    if job.strategy == "pooled":
        raise ValueError("the pooled centralized baseline has no "
                         "federation to attack or robustly aggregate")
    sites = job.task.sites
    if ((spec.robust or plan is not None)
            and resolve_codec(job.compression).name != "none"):
        raise ValueError(
            "robust aggregation and the adversary harness operate on "
            "plaintext fp32 uploads; delta-quantized uploads would fold "
            "attacker-shaped residuals into honest error feedback — use "
            "compression='none'")
    if spec.robust and job.secure_agg:
        raise ValueError(
            "robust rules rank individual uploads; secure aggregation "
            "masks every upload so only their sum is visible — the rule "
            "would rank ciphertext.  Disable secure_agg or use "
            "aggregator='fedavg'")
    if job.max_upload_norm is not None and job.secure_agg:
        raise ValueError(
            "max_upload_norm inspects per-upload L2 norms; secure "
            "aggregation uploads fixed-point ciphertext whose norm is "
            "meaningless — disable one of them")
    if (plan is not None or spec.robust) and job.shard_sites:
        raise ValueError(
            "the sharded engine folds partial sums per device shard and "
            "runs local-strategy contexts — it has neither the full "
            "[S, N] buffer a robust rule needs nor an in-round fault "
            "seam; run robustness jobs with shard_sites=False")
    if spec.rank_based:
        if job.strategy not in ("fedavg", "fedprox"):
            raise ValueError(
                "rank-based robust rules (trimmed/median/krum) combine "
                f"centrally-aggregated uploads; strategy {job.strategy!r} "
                "has no central combine — use fedavg/fedprox (or "
                "aggregator='normclip:c', which gossip honors too)")
        intra_s, inter_s = job.tier_schedulers()
        if (isinstance(intra_s, BufferedScheduler)
                or isinstance(inter_s, BufferedScheduler)):
            raise ValueError(
                "rank-based robust rules need the round's uploads side "
                "by side; a buffered scheduler folds each arrival into a "
                "running sum and discards it — use scheduler='sync'")
        if spec.name == "trimmed" and 2 * spec.f >= sites:
            raise ValueError(
                f"trimmed:{spec.f} discards 2f={2 * spec.f} of {sites} "
                "uploads — the trim must leave a majority (2f < S)")
        if spec.name == "krum" and spec.f > max(sites - 3, 0):
            raise ValueError(
                f"krum:{spec.f} scores each upload against its "
                f"S−f−2 nearest neighbours and needs S ≥ f+3 (S={sites})")
    if (spec.name == "normclip"
            and job.strategy not in ("fedavg", "fedprox", "gcml")):
        raise ValueError(
            "normclip bounds uploads at a central fold (fedavg/fedprox) "
            f"or incoming gossip deltas (gcml), not {job.strategy!r}")
    if (job.round_deadline_s is not None
            and resolve_scheduler(job.scheduler).name != "sync"):
        raise ValueError(
            "round_deadline_s bounds the sync barrier; scheduler "
            f"{job.scheduler!r} has no barrier to bound")


def _validate_down(job: FederatedJob) -> None:
    """Fail-loud composition guards for download compression
    (``down_compression``), shared by every transport.  The download
    codec needs a server that tracks one reference trajectory per site;
    compositions without that server — or whose threat model forbids
    it — are typed errors, never silent dense downgrades."""
    if resolve_codec(job.down_compression).name == "none":
        return
    if job.strategy not in ("fedavg", "fedprox"):
        raise ValueError(
            "down_compression encodes the server's broadcast against "
            "per-site held references; only the centrally-aggregated "
            "strategies (fedavg/fedprox) have that broadcast, not "
            f"{job.strategy!r}")
    if job.secure_agg:
        raise ValueError(
            "secure_agg downloads stay dense: the masked protocol lets "
            "the server materialize only the aggregate sum, while "
            "down_compression requires it to track what each site holds "
            "— disable one of them")
    intra_s, inter_s = job.tier_schedulers()
    if (isinstance(resolve_scheduler(job.scheduler), BufferedScheduler)
            or isinstance(intra_s, BufferedScheduler)
            or isinstance(inter_s, BufferedScheduler)):
        raise ValueError(
            "buffered-async sites pull whichever global version is "
            "newest out of the keep_globals ring, not a per-site "
            "residual stream; down_compression needs scheduler='sync'")
    if job.aggregator_spec.robust or job.adversary_plan is not None:
        raise ValueError(
            "robust aggregation rules and the adversary harness rank "
            "plaintext uploads against ONE shared broadcast; "
            "down_compression gives every site a different decoded "
            "install, so upload distances would mix honest quantization "
            "drift with attacker signal — use down_compression='none'")
    if job.shard_sites:
        raise ValueError(
            "shard_sites=True broadcasts the global through the mesh "
            "collective, not the download codec; run down_compression "
            "jobs with shard_sites=False")


class StackedTransport(Transport):
    """Single-process vmapped simulator (all strategies, all schedulers).

    Rounds run on the compiled scan engine
    (:mod:`repro.core.round_engine`) by default — chunks of rounds fused
    into one donated ``lax.scan`` — with the retired per-round loops
    below kept as the parity oracle (``round_engine="loop"``) and as the
    fallback for the combinations the scan cannot replicate
    (``topk-sparse`` uploads, buffered staleness past the decode ring).
    """

    name = "stacked"

    def execute(self, job: FederatedJob, rounds: int,
                resume: bool = False) -> JobResult:
        scheduler = resolve_scheduler(job.scheduler)
        codec = resolve_codec(job.compression)
        down_codec = resolve_codec(job.down_compression)
        down = down_codec.name != "none"
        buffered = isinstance(scheduler, BufferedScheduler)
        _validate_robustness(job)
        _validate_down(job)
        if job.round_deadline_s is not None:
            raise ValueError(
                "round_deadline_s bounds a real wall-clock barrier; the "
                "stacked simulator has none — run on transport='thread' "
                "or 'tcp'")
        if job.max_upload_norm is not None:
            raise ValueError(
                "max_upload_norm is server-side upload sanitation; the "
                "stacked simulator has no server — run on "
                "transport='thread' or 'tcp'")
        if job.adversary_plan is not None and buffered:
            raise ValueError(
                "the stacked buffered loop trains local-only contexts "
                "with no in-round fault seam; run adversarial buffered "
                "jobs on the thread/tcp transports")
        if job.aggregator_spec.robust and buffered:
            raise ValueError(
                "the stacked buffered loop folds arrivals into a plain "
                "running sum; robust buffered rounds (normclip) run on "
                "the thread/tcp transports' server")
        if job.secure_agg:
            raise ValueError(
                "secure_agg masks real uploads between distrusting "
                "participants — there is no wire to protect inside the "
                "stacked simulator; run it on transport='thread' or 'tcp'")
        topo = job.topo
        if topo.is_pods:
            topo.validate(job.task.sites)
            if job.strategy not in ("fedavg", "fedprox"):
                raise ValueError(
                    "a pods topology needs a centrally-aggregated strategy "
                    f"(fedavg/fedprox), not {job.strategy!r}")
            intra_s, inter_s = job.tier_schedulers()
            if (buffered or isinstance(intra_s, BufferedScheduler)
                    or isinstance(inter_s, BufferedScheduler)):
                raise ValueError(
                    "the stacked simulator runs pods synchronously at both "
                    "tiers; buffered per-tier compositions run on the "
                    "thread/tcp transports")
        if buffered and job.strategy != "fedavg":
            raise ValueError("buffered-async scheduling currently supports "
                             f"fedavg only, not {job.strategy!r}")
        if (not buffered and codec.name != "none"
                and job.strategy not in ("fedavg", "fedprox")):
            raise ValueError(
                "compression on the stacked transport currently supports "
                f"fedavg/fedprox only, not {job.strategy!r}; run gcml "
                "compression on the thread/tcp transports")
        if job.sampled and job.device_data:
            raise ValueError(
                "client sampling precomputes its schedule host-side (a "
                "pure function of (seed, round)); device_data=True "
                "regenerates availability on device and would ignore it — "
                "run sampled jobs with host batches")
        bundle = job.task.build()
        if job.round_engine not in ("auto", "scan", "loop"):
            raise ValueError(f"unknown round_engine {job.round_engine!r}; "
                             "known: auto, scan, loop")
        resume_round = _driver_resume_round(job, resume)
        if job.shard_sites:
            from repro.core import round_engine
            return round_engine.execute_sharded(job, bundle, scheduler,
                                                codec, rounds,
                                                resume_round=resume_round)
        if job.round_engine != "loop":
            from repro.core import round_engine
            res = round_engine.execute_stacked(
                job, bundle, scheduler, codec, rounds,
                resume_round=resume_round,
                down_codec=down_codec if down else None)
            if res is not None:
                return res
            if job.round_engine == "scan":
                raise ValueError(
                    f"round_engine='scan' cannot run this job (codec "
                    f"{codec.name!r} / scheduler {scheduler.name!r} take "
                    "the host path); use round_engine='auto' or 'loop'")
        if job.device_data:
            raise ValueError("device_data=True requires the scan engine")
        if buffered:
            if resume_round is not None:
                raise ValueError(
                    "the buffered host loop carries a mid-round accumulator "
                    "that is not checkpointable; resume buffered jobs on "
                    "the scan engine (round_engine='auto')")
            return self._execute_buffered(job, bundle, scheduler, rounds,
                                          codec)
        if codec.name != "none" or down:
            return self._execute_compressed(
                job, bundle, scheduler, rounds, codec, resume_round,
                down_codec=down_codec if down else None)
        return self._execute_sync(job, bundle, scheduler, rounds,
                                  resume_round)

    def _execute_sync(self, job, bundle, scheduler, rounds,
                      resume_round=None) -> JobResult:
        ctx = job.context(bundle)
        strategy = strat_base.get_strategy(job.strategy)
        state = F.init_fl_state(ctx, bundle.init_fn, jax.random.PRNGKey(job.seed))
        fl_round = F.build_fl_round(ctx)
        fl_step = None                  # AOT-compiled once, timed separately
        compile_s = 0.0
        masks = job.masks(rounds)
        # client sampling: the [rounds, S] 1/π Eq. 1 factor — only
        # threaded when sampling actually thins participation, so dense
        # runs keep a bit-identical round_inputs structure
        wscale = job.weight_scale(rounds) if job.sampled else None
        pair_rng = np.random.default_rng(job.seed)
        recorder = job.recorder(rounds, ctx.fed.num_sites)
        start_round = 0
        if resume_round is not None:
            lmeta = recorder.store.meta("driver_state", resume_round)
            check_engine_tag(lmeta, "sync-loop")
            check_privacy_tag(lmeta, job.dp_tag())
            loaded, _ = recorder.store.load(
                "driver_state", resume_round, {"fl_state": state})
            state = jax.tree.map(jnp.asarray, loaded["fl_state"])
            start_round = resume_round + 1
            # replay the pairing draws the completed rounds consumed, so
            # a resumed gossip schedule continues where the dead run was
            for rr in range(start_round):
                F.make_round_inputs(ctx, rng=pair_rng, round_index=rr,
                                    active=masks[rr])
        for r in range(start_round, rounds):
            b = bundle.round_batches(r, job.local_steps,
                                     pooled=(job.strategy == "pooled"))
            ri = F.make_round_inputs(ctx, rng=pair_rng, round_index=r,
                                     active=masks[r])
            if wscale is not None:
                ri["weight_scale"] = jnp.asarray(wscale[r])
            extra = {}
            if strategy.needs_val_batch:
                ri["dcml_batch"] = jax.tree.map(lambda x: x[:, 0], b)
                ri["val_batch"] = jax.tree.map(lambda x: x[:, -1], b)
            if strategy.needs_pairing:
                extra = {"partner": ri["partner"].tolist(),
                         "is_receiver": ri["is_receiver"].tolist()}
            if fl_step is None:         # warm up: keep compile out of step_s
                t_c = time.perf_counter()
                fl_step = jax.jit(fl_round).lower(state, b, ri).compile()
                compile_s = time.perf_counter() - t_c
            t_step = time.time()
            state, metrics = fl_step(state, b, ri)
            jax.block_until_ready(state)
            extra["step_s"] = time.time() - t_step   # compute-only round time
            recorder.record(r, np.asarray(metrics["loss"]), masks[r],
                            global_fn=lambda: F.global_model(state, ctx),
                            extra=extra)
            recorder.save_state(
                r, lambda: {"fl_state": jax.tree.map(np.asarray, state)},
                meta={"engine": "sync-loop", "dp": job.dp_tag()})
        comm = None
        if job.strategy in ("fedavg", "fedprox"):
            # no wire in-process: report what the equivalent socket run
            # would upload/download (one fp32 model per active site per
            # round, each direction; with pods, plus one partial/global
            # per active pod on the cross-pod link).  A resumed run
            # counts only the rounds it actually executed.
            nbytes = per_site_nbytes(state["params"])
            if ctx.topology.is_pods:
                from repro.core.topology import simulated_pods_comm
                comm = simulated_pods_comm(ctx.topology, masks[start_round:],
                                           nbytes)
            else:
                uploads = int(masks[start_round:].sum())
                comm = {"upload_bytes": uploads * nbytes,
                        "download_bytes": uploads * nbytes,
                        "total_bytes": 2 * uploads * nbytes,
                        "upload_count": uploads, "download_count": uploads,
                        "compression": "none", "down_compression": "none",
                        "simulated": True}
        return recorder.result(F.global_model(state, ctx),
                               transport=self.name, scheduler=scheduler.name,
                               state=state, comm=comm, compile_s=compile_s,
                               resumed_from=resume_round,
                               privacy=job.privacy_report(rounds))

    def _execute_compressed(self, job, bundle, scheduler, rounds,
                            codec, resume_round=None,
                            down_codec=None) -> JobResult:
        """Sync rounds with the upload path routed through the codec:
        every active site's post-training weights are delta-encoded
        against the last broadcast global (error-feedback residual
        carried across rounds), immediately decoded, and folded into the
        :class:`StreamingAccumulator` at the site's case weight — the
        exact client/server path the socket transports drive against the
        ``AggregationServer``, simulated in process.  The first round
        uploads full (quantized) weights; deltas start once a global
        exists, mirroring a server that never saw the initialization.

        With ``down_codec`` (bidirectional compression) the broadcast
        rides the codec seam too: a :class:`DownlinkCompressor` tracks
        each site's held reference server-side and every install is a
        quantized delta decoded through :func:`decode_download`; the
        site's next upload then anchors to its OWN decoded install, and
        a site whose reference left the ``keep_globals`` window
        bootstraps dense both ways (the rejoin rule).  The scan engine's
        ``compressed-scan-bidir`` path is the compiled twin — byte
        accounting is bit-identical on CPU.

        FedProx runs its local half (``fedprox-local``) with the
        proximal anchor re-pinned to each broadcast global; a pods
        topology folds through per-pod accumulators first and combines
        the partials at the pod weights — the simulated twin of the
        :class:`~repro.comms.pods.PodTransport` server stack."""
        local_strategy = ("fedprox-local" if job.strategy == "fedprox"
                          else "individual")
        ctx = job.context(bundle, strategy=local_strategy)  # local-only
        num_sites = ctx.fed.num_sites
        topo = job.topo
        pod_of = topo.pod_of(num_sites)
        state = F.init_fl_state(ctx, bundle.init_fn, jax.random.PRNGKey(job.seed))
        fl_round = F.build_fl_round(ctx)
        local_round = None
        compile_s = 0.0
        masks = job.masks(rounds)
        wscale = job.weight_scale(rounds) if job.sampled else None
        case_w = np.asarray(job.federation().case_weights())
        comps = [UploadCompressor(codec, job.error_feedback)
                 for _ in range(num_sites)]
        down = down_codec is not None and down_codec.name != "none"
        keep = KEEP_GLOBALS_DEFAULT
        engine_tag = "compressed-loop-bidir" if down else "compressed-loop"
        server_down = DownlinkCompressor(down_codec) if down else None
        site_refs: List[Any] = [None] * num_sites   # decoded installs
        down_acked: List[Optional[int]] = [None] * num_sites
        last_active = np.full(num_sites, -keep, np.int64)
        reference = None                     # last broadcast global (fp32)
        global_params = jax.tree.map(np.asarray, F.global_model(state, ctx))
        recorder = job.recorder(rounds, num_sites)
        # the reference/residual like: one site's (unstacked) zero tree
        site_zero = jax.tree.map(lambda x: np.zeros(x.shape[1:], np.float32),
                                 state["params"])
        start_round = 0
        if resume_round is not None:
            lmeta = recorder.store.meta("driver_state", resume_round)
            check_engine_tag(lmeta, engine_tag)
            check_privacy_tag(lmeta, job.dp_tag())
            like = {"fl_state": state, "reference": site_zero,
                    "residuals": [site_zero for _ in range(num_sites)]}
            if down:
                like["down_refs"] = [site_zero for _ in range(num_sites)]
            loaded, _ = recorder.store.load("driver_state", resume_round,
                                            like)
            state = jax.tree.map(jnp.asarray, loaded["fl_state"])
            reference = jax.tree.map(np.asarray, loaded["reference"])
            global_params = reference
            for i, has in enumerate(lmeta.get("has_residual",
                                              [False] * num_sites)):
                if has:
                    comps[i].residual = loaded["residuals"][i]
            if down:
                for i, acked in enumerate(lmeta.get("down_acked",
                                                    [None] * num_sites)):
                    if acked is not None:
                        site_refs[i] = jax.tree.map(np.asarray,
                                                    loaded["down_refs"][i])
                        down_acked[i] = int(acked)
                        server_down.restore(i, site_refs[i], int(acked))
            start_round = resume_round + 1
            # the bootstrap schedule is a pure function of the masks:
            # replay participation so rejoin gaps survive the restart
            for rr in range(start_round):
                last_active[masks[rr]] = rr
        for r in range(start_round, rounds):
            b = bundle.round_batches(r, job.local_steps)
            ri = F.make_round_inputs(ctx, active=masks[r])
            if local_round is None:          # warm up once (compile_s)
                t_c = time.perf_counter()
                local_round = jax.jit(fl_round).lower(state, b, ri).compile()
                compile_s = time.perf_counter() - t_c
            t_step = time.time()
            state, metrics = local_round(state, b, ri)
            jax.block_until_ready(state)
            active_idx = [int(i) for i in np.flatnonzero(masks[r])]
            # two-tier fold: sites stream into their pod's accumulator,
            # pod partials stream into the root at the pod's folded
            # weight (flat topology = the one-accumulator special case)
            pods = [StreamingAccumulator() for _ in range(topo.num_pods)]
            root = StreamingAccumulator()
            round_bytes = 0
            round_down_bytes = 0
            for site in active_idx:
                params_site = jax.tree.map(
                    lambda x: np.asarray(x[site], np.float32), state["params"])
                if down:
                    # bidirectional: the upload anchors to the site's OWN
                    # decoded install; past the keep window both ends
                    # bootstrap dense (matches _bootstrap_masks exactly)
                    up_ref = (None if r - int(last_active[site]) >= keep
                              else site_refs[site])
                else:
                    up_ref = reference
                enc, cmeta = comps[site].encode(params_site, up_ref)
                round_bytes += tree_payload_nbytes(enc)
                w = 1.0 if topo.intra == "uniform" else float(case_w[site])
                if wscale is not None:     # Horvitz–Thompson 1/π factor
                    w *= float(wscale[r, site])
                pods[int(pod_of[site])].fold(
                    decode_upload(enc, cmeta, up_ref), w)
            for acc in pods:
                if acc.count:
                    pw = 1.0 if topo.inter == "uniform" else acc.weight_total
                    root.fold(acc.finalize(), pw)
            if root.count:
                global_params = root.finalize()
                reference = global_params
                if down:
                    # socket ordering: advance the round clock, evict
                    # stale references, THEN serve this round's downloads
                    server_down.evict_stale(r + 1, keep)
                    installs = []
                    for site in active_idx:
                        payload, dmeta = server_down.encode(
                            site, global_params, r + 1,
                            acked_round=down_acked[site])
                        round_down_bytes += tree_payload_nbytes(payload)
                        inst = decode_download(payload, dmeta,
                                               site_refs[site])
                        site_refs[site] = inst
                        down_acked[site] = r + 1
                        installs.append(inst)
                    state = _set_param_rows(state, active_idx, installs)
                else:
                    state = _set_param_sites(state, active_idx, global_params)
                if local_strategy == "fedprox-local":   # Eq. 2 anchor —
                    # the exact global even under down compression (the
                    # scan's vmapped body broadcasts ONE anchor; parity)
                    state = {**state, "strategy": {"global": jax.tree.map(
                        lambda g: jnp.asarray(g, jnp.float32),
                        global_params)}}
            last_active[masks[r]] = r
            extra = {"step_s": time.time() - t_step,
                     "upload_bytes": round_bytes}
            if down:
                extra["download_bytes"] = round_down_bytes
            recorder.record(r, np.asarray(metrics["loss"]), masks[r],
                            global_fn=lambda: global_params, extra=extra)

            def _ckpt_tree(state=state, reference=reference,
                           refs=tuple(site_refs)):
                t = {"fl_state": jax.tree.map(np.asarray, state),
                     "reference": (reference if reference is not None
                                   else site_zero),
                     "residuals": [c.residual if c.residual is not None
                                   else site_zero for c in comps]}
                if down:
                    t["down_refs"] = [rf if rf is not None else site_zero
                                      for rf in refs]
                return t
            meta = {"engine": engine_tag, "dp": job.dp_tag(),
                    "has_residual": [c.residual is not None for c in comps]}
            if down:
                meta["down_acked"] = list(down_acked)
            recorder.save_state(r, _ckpt_tree, meta=meta)
        comm = _compressor_comm(comps, codec,
                                per_site_nbytes(state["params"]),
                                down=server_down,
                                down_name=down_codec.name if down else "none")
        if topo.is_pods:
            from repro.core.topology import simulated_pods_comm
            comm.update(simulated_pods_comm(
                topo, masks[start_round:], per_site_nbytes(state["params"]),
                intra_upload_bytes=comm["upload_bytes"],
                intra_download_bytes=(comm["download_bytes"] if down
                                      else None),
                compression=codec.name,
                down_compression=down_codec.name if down else "none"))
        return recorder.result(global_params, transport=self.name,
                               scheduler=scheduler.name, state=state,
                               comm=comm, compile_s=compile_s,
                               resumed_from=resume_round,
                               privacy=job.privacy_report(rounds))

    def _execute_buffered(self, job, bundle, scheduler, rounds,
                          codec) -> JobResult:
        """FedBuff-style buffered async, simulated: every round all active
        sites train locally, then 'arrive' in random order; each arrival
        folds into the :class:`StreamingAccumulator` at a staleness-
        discounted weight, and the buffer finalizes into a new global
        whenever ``scheduler.ready`` fires (K of S).  After uploading,
        sites pull the latest global — exactly the site loop the socket
        transports run against the buffered ``AggregationServer``.

        With a compression codec, each arrival is delta-encoded against
        the global *version* that site last pulled (a bounded ring of
        recent globals provides the decode references, mirroring the
        server's ``keep_globals`` window) and decoded before the fold.
        """
        from collections import OrderedDict
        ctx = job.context(bundle, strategy="individual")   # local-only rounds
        num_sites = ctx.fed.num_sites
        state = F.init_fl_state(ctx, bundle.init_fn, jax.random.PRNGKey(job.seed))
        fl_round = F.build_fl_round(ctx)
        local_round = None
        compile_s = 0.0
        masks = job.masks(rounds)
        case_w = np.asarray(job.federation().case_weights())
        acc = StreamingAccumulator()
        order_rng = np.random.default_rng(job.seed + 13)
        version = 0
        base_version = np.zeros(num_sites, np.int64)
        global_params = jax.tree.map(np.asarray, F.global_model(state, ctx))
        compress = codec.name != "none"
        comps = [UploadCompressor(codec, job.error_feedback)
                 for _ in range(num_sites)]
        # version → global, the delta decode references, as an O(1) ring:
        # finalize appends, eviction pops the oldest entry — no rebuild
        # scan over the history per arrival.  The init model is version 0
        # (every site starts from it).
        globals_by_version: "OrderedDict[int, Any]" = OrderedDict(
            {0: global_params})
        recorder = job.recorder(rounds, num_sites)
        for r in range(rounds):
            b = bundle.round_batches(r, job.local_steps)
            ri = F.make_round_inputs(ctx, active=masks[r])
            if local_round is None:          # warm up once (compile_s)
                t_c = time.perf_counter()
                local_round = jax.jit(fl_round).lower(state, b, ri).compile()
                compile_s = time.perf_counter() - t_c
            t_step = time.time()
            state, metrics = local_round(state, b, ri)
            jax.block_until_ready(state)
            active_idx = np.flatnonzero(masks[r])
            uploaded: List[int] = []
            for site in order_rng.permutation(active_idx):
                site = int(site)
                discount = scheduler.discount(version - int(base_version[site]))
                if discount is None:                 # too stale: resync only
                    state = _set_param_sites(state, [site], global_params)
                    base_version[site] = version
                    continue
                upload = jax.tree.map(
                    lambda x: np.asarray(x[site], np.float32), state["params"])
                if compress:
                    ref = globals_by_version.get(int(base_version[site]))
                    enc, cmeta = comps[site].encode(upload, ref)
                    upload = decode_upload(enc, cmeta, ref)
                acc.fold(upload, float(case_w[site]) * discount)
                uploaded.append(site)
                if scheduler.ready(acc.count, len(active_idx)):
                    global_params = acc.finalize()
                    version += 1
                    if compress:
                        globals_by_version[version] = global_params
                        while len(globals_by_version) > KEEP_GLOBALS_DEFAULT:
                            globals_by_version.popitem(last=False)
            if uploaded:                             # pull latest global
                state = _set_param_sites(state, uploaded, global_params)
                base_version[np.asarray(uploaded)] = version
            recorder.record(r, np.asarray(metrics["loss"]), masks[r],
                            global_fn=lambda: global_params,
                            extra={"version": version,
                                   "step_s": time.time() - t_step})
        comm = (_compressor_comm(comps, codec,
                                 per_site_nbytes(state["params"]))
                if compress else None)
        return recorder.result(global_params, transport=self.name,
                               scheduler=scheduler.name, state=state,
                               comm=comm, compile_s=compile_s,
                               privacy=job.privacy_report(rounds))




def _compressor_comm(comps: List[UploadCompressor], codec: Codec,
                     download_nbytes: int,
                     down: Optional[DownlinkCompressor] = None,
                     down_name: str = "none") -> Dict[str, Any]:
    """Aggregate compressor counters into the JobResult comm dict
    (stacked simulator: payload bytes, no framing/header overhead).
    Without a :class:`DownlinkCompressor` downloads are uncompressed
    fp32 (one dense global per upload)."""
    uploads = sum(c.encodes for c in comps)
    up_bytes = sum(c.encoded_bytes for c in comps)
    if down is not None:
        down_bytes, down_raw = down.encoded_bytes, down.raw_bytes
        down_count = down.encodes
    else:
        down_bytes = down_raw = uploads * download_nbytes
        down_count = uploads
    return {"upload_bytes": up_bytes,
            "upload_raw_bytes": sum(c.raw_bytes for c in comps),
            "download_bytes": down_bytes,
            "download_raw_bytes": down_raw,
            "total_bytes": up_bytes + down_bytes,
            "upload_count": uploads, "download_count": down_count,
            "compression": codec.name, "down_compression": down_name,
            "simulated": True}


def _set_param_sites(fl_state, sites: List[int], global_tree):
    """Overwrite the given site rows of the stacked params with the
    (unstacked) global model."""
    idx = jnp.asarray(sites)
    new_params = jax.tree.map(
        lambda x, g: x.at[idx].set(jnp.asarray(np.asarray(g)).astype(x.dtype)),
        fl_state["params"], global_tree)
    return {**fl_state, "params": new_params}


def _set_param_rows(fl_state, sites: List[int], trees: List[Any]):
    """Overwrite the given site rows of the stacked params with per-site
    (unstacked) model trees — the bidirectional-compression install,
    where every site decodes a different model."""
    if not sites:
        return fl_state
    idx = jnp.asarray(sites)
    stacked = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x, np.float32) for x in xs]), *trees)
    new_params = jax.tree.map(
        lambda x, g: x.at[idx].set(jnp.asarray(g).astype(x.dtype)),
        fl_state["params"], stacked)
    return {**fl_state, "params": new_params}


# -- socket transports (real Peer / AggregationServer / CoordinationServer) --


def _site_host_tree(params_stacked):
    """Site 0 of a [1, …]-stacked tree as host numpy (the wire payload)."""
    return jax.tree.map(lambda x: np.asarray(x[0]), params_stacked)


def _run_site(job: FederatedJob, site_id: int, agg_addr, coord_addr,
              rounds: int, start_round: int = 0) -> Dict[str, Any]:
    """One site's FL script — identical whether driven by a thread or an
    OS process (paper Algorithm 1, site side), and identical under a
    pods topology: the site just talks to its pod's aggregation server
    (``agg_addr`` arrives as a site→address map) and counts its barrier
    against its pod's active members.

    With a ``checkpoint_dir`` the site keeps its own sub-store
    (``checkpoint_dir/site{id}``: fl_state + delta reference + EF
    residual every ``ckpt_every`` rounds) and, when the driver resumes
    it at ``start_round > 0``, reloads round ``start_round - 1`` and
    re-enters mid-job.  With a ``lease_ttl`` it holds a lease at its
    aggregation point via a heartbeat thread; if admitted after the job
    advanced (a late joiner), it bootstraps from the join reply's dense
    global and skips the completed rounds."""
    from repro.comms.peer import Peer
    bundle = job.task.build()
    if isinstance(agg_addr, dict):          # pods: my pod server's address
        agg_addr = tuple(agg_addr[site_id])
    # the scheduler a site experiences is its aggregation point's — the
    # intra-pod tier under a pods topology (= the job scheduler when flat)
    buffered = isinstance(job.tier_schedulers()[0], BufferedScheduler)
    local_strategy = ("fedprox-local" if job.strategy == "fedprox"
                      else "individual")
    ctx = job.context(bundle, num_sites=1, strategy=local_strategy,
                      dp_site_base=site_id)
    dp_on = job.dp is not None
    state = F.init_fl_state(ctx, bundle.init_fn, jax.random.PRNGKey(job.seed))
    local_round = jax.jit(F.build_fl_round(ctx))
    # every site replays the same Algorithm-2 chain (site + pod tiers) —
    # no status traffic needed for the schedule itself
    masks = job.masks(rounds)
    pod_members = job.topo.pod_of(job.task.sites) == \
        job.topo.pod_of(job.task.sites)[site_id]     # my barrier's peers
    strategy = strat_base.get_strategy(job.strategy)
    dcml_step = None
    peer = Peer(site_id, wire=job.wire)
    ri1 = {"active": np.ones(1, bool), "partner": np.zeros(1, np.int64),
           "is_receiver": np.zeros(1, bool)}
    losses: List[float] = []
    base_round = start_round  # server round of the global this site holds
    stale_uploads = 0
    rejected_uploads = 0
    # deterministic Byzantine harness: whether THIS worker is in the
    # plan's seeded malicious set is a pure function of (seed, S), so
    # every transport replays the same adversary without negotiation
    plan = job.adversary_plan
    malicious = plan is not None and plan.is_malicious(site_id,
                                                       job.task.sites)
    # upload compression: one compressor per outgoing stream, so the
    # error-feedback residuals compensate the right channel
    codec = resolve_codec(job.compression)
    comp = (UploadCompressor(codec, job.error_feedback)
            if codec.name != "none" else None)
    peer_comp = (UploadCompressor(codec, job.error_feedback)
                 if codec.name != "none" and strategy.needs_pairing else None)
    # download compression: the server streams per-site quantized deltas
    # against the global this site last acknowledged (meta carries the
    # ack); the decoded install doubles as the upload/prox anchor, which
    # is bit-equal to the server's held copy by construction
    down = resolve_codec(job.down_compression).name != "none"
    down_ref = None         # last decoded install (the delta base)
    down_acked: Optional[int] = None
    reference = None        # last pulled global (fp32) — the delta anchor
    sa = None               # secure aggregation: pairwise upload masker
    sa_bytes = sa_raw = sa_count = 0
    if job.secure_agg:
        from repro.privacy import SecureAggClient
        sa = SecureAggClient(job.mask_secret, "site", site_id)
        case_w = np.asarray(job.federation().case_weights())
        sa_weight = (1.0 if job.topo.intra == "uniform"
                     else float(case_w[site_id]))
    site_store = None
    if job.checkpoint_dir:
        from repro.checkpoint import CheckpointStore
        site_store = CheckpointStore(
            Path(job.checkpoint_dir) / f"site{site_id}")
    # the reference/residual checkpoint like: this site's zero model tree
    site_zero = jax.tree.map(lambda x: np.zeros(x.shape[1:], np.float32),
                             state["params"])
    hb = None
    try:
        if start_round > 0 and site_store is not None:
            like = {"fl_state": state}
            if comp is not None:
                like["reference"] = site_zero
                like["residual"] = site_zero
            if down:
                like["down_ref"] = site_zero
            loaded, lmeta = site_store.load("state", start_round - 1, like)
            state = jax.tree.map(jnp.asarray, loaded["fl_state"])
            base_round = int(lmeta.get("base_round", start_round))
            if comp is not None:
                if lmeta.get("has_reference"):
                    reference = jax.tree.map(np.asarray, loaded["reference"])
                if lmeta.get("has_residual"):
                    comp.residual = jax.tree.map(np.asarray,
                                                 loaded["residual"])
            if down and lmeta.get("has_down_ref"):
                # re-enter the server's residual stream exactly where the
                # killed site left it (the server restored the matching
                # held copy from its own checkpoint)
                down_ref = jax.tree.map(np.asarray, loaded["down_ref"])
                acked = lmeta.get("down_acked")
                down_acked = int(acked) if acked is not None else None
        if job.lease_ttl and agg_addr is not None:
            from repro.comms.membership import HeartbeatClient
            hb = HeartbeatClient(
                site_id, lambda k, m: peer.request(agg_addr, k, m),
                job.lease_ttl).start()
            join_round = int(hb.join_meta.get("round", 0))
            if join_round > start_round and hb.bootstrap is not None:
                # late joiner: the job is join_round rounds in — adopt the
                # dense bootstrap global and skip the completed rounds
                g = hb.bootstrap
                state = {**state, "params": jax.tree.map(
                    lambda x, gg: jnp.broadcast_to(
                        jnp.asarray(gg).astype(x.dtype)[None], x.shape),
                    state["params"], g)}
                if local_strategy == "fedprox-local":
                    state = {**state, "strategy": {"global": jax.tree.map(
                        lambda gg: jnp.asarray(gg, jnp.float32), g)}}
                base_round = join_round
                if comp is not None:
                    reference = jax.tree.map(
                        lambda x: np.asarray(x, np.float32), g)
                losses.extend([float("nan")] * (join_round - start_round))
                start_round = join_round
        if strategy.needs_pairing:
            from repro.core.strategies.gcml import make_site_dcml
            dcml_step = jax.jit(make_site_dcml(job.context(bundle)))
            peer.register(coord_addr)
        for r in range(start_round, rounds):
            me_active = bool(masks[r, site_id])
            b = bundle.site_batches(site_id, r, job.local_steps)
            if malicious and plan.flips_labels:
                b = plan.perturb_batch(b)
            # -- decentralized pre-exchange: gossip + regional DCML ------
            if dcml_step is not None and me_active:
                asg = peer.get_assignment(coord_addr, r + 1)
                recv_of = {int(asg["partner"][j]): j
                           for j in range(len(asg["partner"]))
                           if asg["is_receiver"][j]}
                if asg["is_sender"][site_id]:
                    target = recv_of[site_id]
                    wire_tree = _site_host_tree(state["params"])
                    if malicious and plan.flips_params:
                        # P2P: the pushed model is this site's "upload"
                        wire_tree = plan.perturb_tree(wire_tree, site_id, r)
                    smeta = None
                    if peer_comp is not None:   # quantize the P2P push too
                        wire_tree, smeta = peer_comp.encode(wire_tree)
                    peer.send_model(tuple(asg["addresses"][str(target)]),
                                    wire_tree, r + 1, meta_extra=smeta)
                if asg["is_receiver"][site_id]:
                    imeta, incoming = peer.recv_model(timeout=job.io_timeout)
                    incoming = decode_upload(incoming, imeta)
                    merged, _ = dcml_step(
                        stacking.site_slice(state["params"], 0),
                        jax.tree.map(jnp.asarray, incoming),
                        jax.tree.map(lambda x: x[0, 0], b),
                        jax.tree.map(lambda x: x[0, -1], b))
                    state = {**state,
                             "params": stacking.broadcast_to_sites(merged, 1)}
            # -- local training ------------------------------------------
            if me_active or job.dropout_scenario == "disconnect":
                if dp_on:
                    # pin the carried round counter to the loop round: a
                    # shut-down or late-joining site skips rounds, and its
                    # DP noise stream must skip with it to match the
                    # stacked twin
                    state = {**state, "round": jnp.asarray(r, jnp.int32)}
                state, metrics = local_round(state, b, ri1)
                losses.append(float(np.asarray(metrics["loss"])[0]))
            else:                                    # workstation off
                losses.append(float("nan"))
            # -- centralized exchange: upload → aggregate → download -----
            if agg_addr is not None and me_active:
                # sync barrier rounds are tagged with the loop round; under
                # a buffered scheduler the server finalizes ~S/K times per
                # loop round, so the upload carries the round of the global
                # this site last pulled — the FedBuff staleness anchor
                upload_round = base_round + 1 if buffered else r + 1
                payload = _site_host_tree(state["params"])
                if malicious and plan.flips_params:
                    # same seam as the stacked engines: only the WIRE
                    # payload at round r is perturbed — the site's own
                    # state stays honest, matching the traced round body
                    # where post_exchange overwrites the poisoned rows
                    payload = plan.perturb_tree(payload, site_id, r)
                cmeta = None
                if sa is not None:
                    # mask against the round's *scheduled* barrier peers
                    # (every participant replays masks, so the set needs
                    # no negotiation); the server recovers the pair seeds
                    # of anyone scheduled who never arrives
                    sa_raw += tree_payload_nbytes(payload)
                    participants = np.flatnonzero(masks[r] & pod_members)
                    payload, cmeta = sa.encode(payload, sa_weight,
                                               participants, r)
                    sa_bytes += tree_payload_nbytes(payload)
                    sa_count += 1
                elif comp is not None:
                    # a site that sat out long enough for its reference
                    # global to leave the server's keep_globals window
                    # must re-send dense: under the sync barrier a
                    # stale-acked (unfoldable) delta would leave the
                    # round one upload short of `expected` forever
                    if (reference is not None
                            and upload_round - base_round
                            >= KEEP_GLOBALS_DEFAULT):
                        reference = None
                    payload, cmeta = comp.encode(payload, reference)
                    cmeta["base_round"] = base_round if reference is not None \
                        else 0
                ack = peer.upload(agg_addr, payload, upload_round,
                                  active_sites=int(masks[r][pod_members].sum()),
                                  meta_extra=cmeta)
                if ack.get("rejected"):
                    # server-side sanitation refused the fold.  Drop any
                    # error-feedback residual: compensating next round
                    # for an upload the server never folded would
                    # re-inject the rejected content
                    rejected_uploads += 1
                    if comp is not None:
                        comp.residual = None
                elif ack.get("stale"):
                    # rejected as too stale: the resync below restores a
                    # small staleness for the next upload
                    stale_uploads += 1
                # buffered async has no barrier at all: pull whatever global
                # is current (want=0) rather than waiting for a window that
                # sites which already finished their rounds may never fill;
                # sync keeps the round-(r+1) barrier
                want = 0 if buffered else r + 1
                g, dmeta = peer.download(agg_addr, want, with_meta=True,
                                         down=down, acked_round=down_acked)
                if g is not None:        # None only if no buffer finalized yet
                    if down:
                        # delta broadcast: decode against the held install;
                        # dense (bootstrap / ack mismatch) decodes are
                        # reference-free and restart the stream
                        g = decode_download(g, dmeta, down_ref)
                        down_ref = jax.tree.map(
                            lambda x: np.asarray(x, np.float32), g)
                        down_acked = int(dmeta["round"])
                    base_round = int(dmeta["round"])
                    if comp is not None:     # next delta anchors to this pull
                        reference = jax.tree.map(
                            lambda x: np.asarray(x, np.float32), g)
                    new_params = jax.tree.map(
                        lambda x, gg: jnp.broadcast_to(
                            jnp.asarray(gg).astype(x.dtype)[None], x.shape),
                        state["params"], g)
                    state = {**state, "params": new_params}
                    if local_strategy == "fedprox-local":  # Eq. 2 anchor
                        state = {**state, "strategy": {
                            "global": jax.tree.map(
                                lambda gg: jnp.asarray(gg, jnp.float32), g)}}
            # -- crash-resume checkpoint (end-of-round state) ------------
            if site_store is not None and r % job.ckpt_every == 0:
                tree = {"fl_state": jax.tree.map(np.asarray, state)}
                if comp is not None:
                    tree["reference"] = (reference if reference is not None
                                         else site_zero)
                    tree["residual"] = (comp.residual
                                        if comp.residual is not None
                                        else site_zero)
                if down:
                    tree["down_ref"] = (down_ref if down_ref is not None
                                        else site_zero)
                site_store.save(
                    "state", r, tree,
                    meta={"base_round": base_round,
                          "has_reference": comp is not None
                          and reference is not None,
                          "has_residual": comp is not None
                          and comp.residual is not None,
                          "has_down_ref": down and down_ref is not None,
                          "down_acked": down_acked})
        streams = [c for c in (comp, peer_comp) if c is not None]
        return {"losses": losses, "stale_uploads": stale_uploads,
                "rejected_uploads": rejected_uploads,
                "params": _site_host_tree(state["params"]),
                "upload_payload_bytes":
                    sum(c.encoded_bytes for c in streams) + sa_bytes,
                "upload_raw_bytes":
                    sum(c.raw_bytes for c in streams) + sa_raw,
                "upload_count": sum(c.encodes for c in streams) + sa_count}
    finally:
        if hb is not None:
            hb.stop(leave=True)
        peer.close()


def _site_worker(job, site_id, agg_addr, coord_addr, result_q, rounds,
                 start_round=0):
    """Queue-reporting wrapper around :func:`_run_site` (thread/process)."""
    try:
        result_q.put((site_id, _run_site(job, site_id, agg_addr, coord_addr,
                                         rounds, start_round)))
    except Exception as e:  # noqa: BLE001 — surface worker death to the job
        result_q.put((site_id, {"error": f"{type(e).__name__}: {e}"}))


class _SocketTransport(Transport):
    """Shared round-trip machinery for thread- and process-backed sites.

    Round history is assembled from the workers' reports after the run:
    per-round ``wall_s`` is the run mean (the driver cannot observe
    individual remote rounds), and checkpointing saves the final global
    model only.
    """

    name = "socket"

    def execute(self, job: FederatedJob, rounds: int,
                resume: bool = False) -> JobResult:
        scheduler = resolve_scheduler(job.scheduler)
        strategy = strat_base.get_strategy(job.strategy)
        topo = job.topo
        if job.shard_sites:
            raise ValueError("shard_sites=True shards the stacked "
                             "simulator's [S, N] buffer; socket transports "
                             "distribute sites as processes already — use "
                             "transport='stacked'")
        if job.strategy == "pooled":
            raise ValueError("pooled is a single-process baseline; "
                             "run it on the stacked transport")
        if strategy.needs_pairing and job.max_dropout:
            raise ValueError("gossip under dropout needs coordinated status "
                             "updates; run it on the stacked transport")
        if topo.is_pods and job.strategy not in ("fedavg", "fedprox"):
            raise ValueError(
                "a pods topology needs a centrally-aggregated strategy "
                f"(fedavg/fedprox), not {job.strategy!r}")
        if job.secure_agg:
            intra_s, inter_s = job.tier_schedulers()
            if (isinstance(intra_s, BufferedScheduler)
                    or isinstance(inter_s, BufferedScheduler)):
                raise ValueError(
                    "secure aggregation cancels pairwise masks at a sync "
                    "barrier over the round's scheduled participants; "
                    "buffered-async folds partial subsets, so the masks "
                    "would never cancel")
            if resolve_codec(job.compression).name != "none":
                raise ValueError(
                    "secure aggregation uploads fixed-point masked "
                    "integers; quantizing that ciphertext would corrupt "
                    "the modular sum — use compression='none'")
            if job.strategy not in ("fedavg", "fedprox"):
                raise ValueError(
                    "secure aggregation protects centrally-aggregated "
                    f"uploads (fedavg/fedprox), not {job.strategy!r}")
        _validate_robustness(job)
        _validate_down(job)
        if job.round_deadline_s is not None:
            if topo.is_pods:
                raise ValueError(
                    "round_deadline_s bounds the flat star's sync "
                    "barrier; per-tier pod deadlines are not wired — "
                    "use topology='flat'")
            # the deadline rides the scheduler so the server's watcher
            # thread can read it off its own round policy
            scheduler = SyncScheduler(round_deadline_s=job.round_deadline_s)
        fed = job.federation()
        num_sites = fed.num_sites
        start_round = 0
        resumed_from = None
        initial_global = None
        if resume:
            if not job.checkpoint_dir:
                raise ValueError("run(resume=True) needs checkpoint_dir set")
            resumed_from, initial_global = _socket_resume_point(job,
                                                                num_sites)
            if resumed_from is not None:
                start_round = resumed_from + 1
        down_codec = resolve_codec(job.down_compression)
        down = down_codec.name != "none"
        initial_down = None
        if down and resumed_from is not None:
            # the resumed server must encode deltas against exactly what
            # each resumed site holds, or trajectories diverge
            initial_down = _socket_down_refs(job, resumed_from, num_sites)
        # construct before the workers run so wall_s spans the actual run
        recorder = job.recorder(rounds, num_sites)
        from repro.comms.coordinator import (AggregationServer,
                                             CoordinationServer)
        servers = []
        agg = None
        pod_stack = None
        agg_addr = coord_addr = None
        try:
            if topo.is_pods:
                from repro.comms.pods import PodTransport
                intra_s, inter_s = job.tier_schedulers()
                pod_stack = PodTransport(
                    topo, num_sites, list(fed.case_weights()),
                    job.masks(rounds), intra_s, inter_s,
                    io_timeout=job.io_timeout, wire=job.wire,
                    lease_ttl=job.lease_ttl, start_round=start_round,
                    initial_global=initial_global,
                    ckpt_store=recorder.store,
                    ckpt_every=job.ckpt_every,
                    codec=resolve_codec(job.compression),
                    error_feedback=job.error_feedback,
                    aggregator=job.aggregator,
                    max_upload_norm=job.max_upload_norm,
                    down_codec=down_codec if down else None,
                    initial_down=initial_down,
                    mask_secret=(job.mask_secret if job.secure_agg
                                 else None)).start()
                servers.append(pod_stack)
                agg_addr = pod_stack.site_addrs()
            elif not strategy.needs_pairing and job.strategy != "individual":
                sa_state = None
                if job.secure_agg:
                    from repro.privacy import SecureAggState
                    sa_state = SecureAggState(job.mask_secret, "site",
                                              job.masks(rounds))
                agg = AggregationServer(
                    "127.0.0.1", 0, num_sites=num_sites,
                    case_weights=list(fed.case_weights()),
                    download_timeout=job.io_timeout / 2,
                    scheduler=scheduler, wire=job.wire,
                    lease_ttl=job.lease_ttl, initial_round=start_round,
                    initial_global=initial_global,
                    ckpt_store=recorder.store, ckpt_every=job.ckpt_every,
                    secure_agg=sa_state, aggregator=job.aggregator,
                    max_upload_norm=job.max_upload_norm,
                    down_compression=down_codec if down else None,
                    initial_down=initial_down)
                servers.append(agg)
                agg_addr = agg.addr
            if strategy.needs_pairing:
                coord = CoordinationServer("127.0.0.1", 0,
                                           num_sites=num_sites, seed=job.seed,
                                           wire=job.wire)
                servers.append(coord)
                coord_addr = coord.addr
            results = self._run_workers(job, num_sites, agg_addr, coord_addr,
                                        rounds, start_round)
        finally:
            for s in servers:
                s.stop()
        per_site = dict(results)
        dead = {i: p["error"] for i, p in per_site.items() if "error" in p}
        if pod_stack is not None and pod_stack.leader_errors:
            dead = {**dead, **{f"pod-leader-{p}": e
                               for p, e in pod_stack.leader_errors.items()}}
        if dead:
            # elastic federation (lease_ttl set): a dead SITE already fell
            # out of the barriers via lease expiry — finish without it.
            # Dead infrastructure (a pod-leader relay) still aborts.
            elastic = (job.lease_ttl is not None
                       and all(isinstance(k, int) for k in dead))
            if not elastic:
                raise RuntimeError(f"site workers failed: {dead}")
            if job.verbose:
                print(f"elastic: finishing without failed sites "
                      f"{sorted(dead)}")
        # bytes-on-the-wire accounting: server-side counters are the real
        # framed bytes; site counters are the encoded payload (covers the
        # serverless gossip P2P pushes too)
        codec = resolve_codec(job.compression)
        site_payload = sum(p.get("upload_payload_bytes", 0)
                           for p in per_site.values())
        site_raw = sum(p.get("upload_raw_bytes", 0) for p in per_site.values())
        site_count = sum(p.get("upload_count", 0) for p in per_site.values())
        comm = None
        if pod_stack is not None:            # two-tier: per-tier byte split
            comm = {**pod_stack.comm(codec.name, down_codec.name),
                    "site_payload_bytes": site_payload,
                    "upload_raw_bytes": site_raw}
        elif agg is not None:
            snap = agg.stats.snapshot()
            up_b = snap.get("upload", {}).get("in_bytes", 0)
            down_b = snap.get("download", {}).get("out_bytes", 0)
            comm = {"upload_bytes": up_b,
                    "download_bytes": down_b,
                    "total_bytes": up_b + down_b,
                    "upload_count": snap.get("upload", {}).get("count", 0),
                    "download_count":
                        snap.get("download", {}).get("count", 0),
                    "site_payload_bytes": site_payload,
                    "upload_raw_bytes": site_raw,
                    "compression": codec.name,
                    "down_compression": down_codec.name, "simulated": False}
            down_counters = agg.down_counters
            if down_counters is not None:
                # payload-level split for the ratio math (out_bytes above
                # additionally includes wire framing)
                comm["download_payload_bytes"] = down_counters["encoded"]
                comm["download_raw_bytes"] = down_counters["raw"]
        elif site_count:                     # gossip P2P, compressed
            comm = {"upload_bytes": site_payload,
                    "upload_raw_bytes": site_raw, "download_bytes": 0,
                    "total_bytes": site_payload, "upload_count": site_count,
                    "download_count": 0, "compression": codec.name,
                    "down_compression": "none", "simulated": False}
        exec_rounds = rounds - start_round
        nan_row = [float("nan")] * exec_rounds
        losses = np.stack([per_site[i].get("losses", nan_row)
                           for i in range(num_sites)])
        masks = job.masks(rounds)
        stale = [per_site[i].get("stale_uploads", 0) for i in range(num_sites)]
        # server-authoritative sanitation count (covers decode failures a
        # site never learned the reason for); sites report their own view
        # in the per-site dicts for tests
        rejected = 0
        if pod_stack is not None:
            rejected = pod_stack.rejected_uploads
        elif agg is not None:
            rejected = agg.rejected_uploads
        round_wall = recorder.elapsed / max(exec_rounds, 1)
        for ri, r in enumerate(range(start_round, rounds)):
            extra = {"wall_s": round_wall}
            if r == rounds - 1:
                extra["stale_uploads"] = stale
            recorder.record(r, losses[:, ri], masks[r], extra=extra)
        # the served global: case-weighted mean of the final site models
        # (for FedAvg the sites already hold the last broadcast global);
        # an elastic run folds the survivors only
        acc = StreamingAccumulator()
        cw = fed.case_weights()
        for i in range(num_sites):
            if "params" in per_site[i]:
                acc.fold(per_site[i]["params"], float(cw[i]))
        if not acc.count:
            raise RuntimeError(f"no site produced a final model: {dead}")
        global_params = acc.finalize()
        if recorder.store is not None:       # --checkpoint: final global
            recorder.store.save("global", rounds - 1, global_params)
        return recorder.result(global_params, transport=self.name,
                               scheduler=scheduler.name, comm=comm,
                               resumed_from=resumed_from,
                               rejected_uploads=rejected,
                               privacy=job.privacy_report(rounds))

    def _run_workers(self, job, num_sites, agg_addr, coord_addr, rounds,
                     start_round=0):
        raise NotImplementedError


class ThreadTransport(_SocketTransport):
    """Real TCP round trips, sites driven by in-process threads."""

    name = "thread"

    def _run_workers(self, job, num_sites, agg_addr, coord_addr, rounds,
                     start_round=0):
        q: "queue.Queue" = queue.Queue()
        threads = [threading.Thread(
            target=_site_worker,
            args=(job, i, agg_addr, coord_addr, q, rounds, start_round),
            daemon=True)
            for i in range(num_sites)]
        for t in threads:
            t.start()
        results = [q.get(timeout=job.io_timeout * max(rounds, 1))
                   for _ in range(num_sites)]
        for t in threads:
            t.join(timeout=5)
        return results


class TcpTransport(_SocketTransport):
    """Real TCP round trips, one OS process per site (paper §III.A.3:
    sites identified by IP:port, colocated or spread across machines).

    A CPU-host transport: a TPU chip belongs to one process at a time,
    and this process (the aggregation server) already holds it, so site
    processes spawned here could not reach it.  On a TPU host it refuses
    before spawning; the chip path is the stacked transport."""

    name = "tcp"

    def execute(self, job: FederatedJob, rounds: int,
                resume: bool = False) -> JobResult:
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "transport='tcp' runs one JAX process per site, but a TPU "
                "chip belongs to one process at a time and this process "
                "already holds it; use transport='stacked' on a TPU host, "
                "or run the tcp transport on CPU hosts (JAX_PLATFORMS=cpu)")
        return super().execute(job, rounds, resume)

    def _run_workers(self, job, num_sites, agg_addr, coord_addr, rounds,
                     start_round=0):
        import multiprocessing as mp
        import queue as queue_mod
        import time as time_mod
        mpctx = mp.get_context("spawn")
        q = mpctx.Queue()
        procs = [mpctx.Process(
            target=_site_worker,
            args=(job, i, agg_addr, coord_addr, q, rounds, start_round),
            daemon=True)
            for i in range(num_sites)]
        for p in procs:
            p.start()
        results: List[Tuple[int, Dict[str, Any]]] = []
        deadline = time_mod.time() + job.io_timeout * max(rounds, 1)
        try:
            while len(results) < num_sites:
                try:
                    results.append(q.get(timeout=2.0))
                except queue_mod.Empty:
                    # a worker that died before reporting would stall the
                    # collection until the deadline — fail fast instead
                    reported = {i for i, _ in results}
                    dead = [i for i, p in enumerate(procs)
                            if not p.is_alive()
                            and p.exitcode not in (0, None)
                            and i not in reported]
                    if dead and q.empty():
                        if job.lease_ttl is not None:
                            # elastic: a killed site never reports — its
                            # lease expiry already unblocked the
                            # survivors, so stand in an error record and
                            # keep collecting the rest
                            for i in dead:
                                results.append((i, {
                                    "error": f"process exited "
                                             f"{procs[i].exitcode}"}))
                            continue
                        raise RuntimeError(
                            f"{len(dead)} site process(es) exited with "
                            f"{[procs[i].exitcode for i in dead]} before "
                            f"reporting")
                    if time_mod.time() > deadline:
                        raise TimeoutError(
                            f"collected {len(results)}/{num_sites} site "
                            f"results before timeout")
        finally:
            for p in procs:
                p.join(timeout=30)
                if p.is_alive():
                    p.terminate()
        return results


_TRANSPORTS = {"stacked": StackedTransport, "thread": ThreadTransport,
               "tcp": TcpTransport}


def resolve_transport(spec: Union[str, Transport, None]) -> Transport:
    if spec is None:
        return StackedTransport()
    if isinstance(spec, Transport):
        return spec
    try:
        return _TRANSPORTS[spec]()
    except KeyError:
        raise KeyError(f"unknown transport {spec!r}; known: "
                       f"{sorted(_TRANSPORTS)}")
