"""Compiled round engine — K federated rounds in ONE jitted ``lax.scan``.

The paper's headline efficiency claim is wall-clock: parallel federated
rounds cut 86.2 h of sequential training to 13.4 h.  Our scale workhorse
for that claim is the stacked (vmapped) simulator, but its per-round
loop re-entered Python every round: a jit dispatch + ``block_until_ready``
per round, host-generated batches/masks, and — on the compressed and
buffered paths — a per-site device→host copy folded through a numpy
accumulator.  At simulator scale the machine was gated by dispatch and
PCIe, not FLOPs.

This module compiles the loop away.  ``execute_stacked`` runs the job as
a sequence of *chunks*; each chunk is one jitted ``lax.scan`` over
``chunk_rounds`` federated rounds with the carry (fl_state + engine
buffers) **donated**, per-round losses/metrics accumulated into a
``[K, S]`` device buffer and fetched once per chunk:

  * **sync** rounds (every strategy incl. GCML gossip and the pooled
    baseline) scan the existing jitted ``fl_round`` body; host inputs
    (Algorithm-2 masks, gossip pairings, synthetic batches) are
    precomputed per chunk and transferred once — or, with
    ``device_data=True`` on token tasks, produced *on device* from a
    threaded jax PRNG (``make_round_inputs_traced`` +
    ``TokenTaskGenerator.traced_stacked_batches``) so a chunk runs with
    zero host↔device traffic beyond the loss buffer;
  * **compressed** rounds (int8/fp8/topk-fixed, fedavg or fedprox) keep
    simulated compression entirely on device: error-feedback residuals
    ride the scan as ``[S, …]`` state, quantize→dequantize runs through
    the ``kernels/quantize.py`` math (Pallas kernel on TPU/GPU —
    including the fused dequantize+weighted-fold ``fedagg_dequant`` so
    dense per-site models never hit HBM — pure-jnp twin on CPU,
    bit-identical to the numpy wire codec) or the ``jax.lax.top_k``
    exact-k sparsifier, FedProx's proximal anchor re-pins to each
    broadcast global inside the scan (``fedprox-local``), and the fold
    goes through ``AggregationEngine``'s padded ``[S, N]`` buffer — the
    two-tier segment-reduce when the job has a pods topology — instead
    of the host ``StreamingAccumulator``;
  * **buffered** (FedBuff) rounds trace the arrival loop itself: the
    per-round upload order is precomputed host-side (same RNG stream as
    the retired loop), and staleness discounts, K-of-S finalization,
    version counters and the bounded ``keep_globals`` ring of decode
    references are all device state inside the scan.

Chunk boundaries align with checkpoint rounds (the only places a global
model must materialize).  Each chunk runs under ``repro.telemetry`` spans:
``repro.chunk`` around the host iteration (``RoundRecorder.chunk``), with
``repro.chunk.inputs`` (its ``bytes`` put on the device),
``repro.chunk.execute`` (dispatch to ``block_until_ready``) and
``repro.chunk.record`` inside, and ``repro.chunk.lower`` /
``repro.chunk.compile`` once per chunk shape.  ``JobResult.compile_s`` is
the sum of the lower and compile spans; a round's ``step_s`` is its share
of the execute span and its ``wall_s`` its share of the ``repro.chunk``
span less the lowering and compiling inside it.

The host path is still taken for: the ``topk-sparse`` codec (data-
dependent index payloads — the fixed-k ``topk-fixed`` variant
compiles), buffered runs whose ``max_staleness`` reaches past the
``keep_globals`` ring (or that use a top-k codec), and
``round_engine="loop"`` — the retired per-round driver kept in
``repro.api`` as the parity oracle for tests and benchmarks.  Socket
transports are untouched.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.comms.compression import KEEP_GLOBALS_DEFAULT
from repro.core import federation as F
from repro.core import stacking
from repro.core.agg_engine import (get_engine, normalized_weights,
                                   per_site_nbytes)
from repro.core.session import (BufferedScheduler, JobResult,
                                check_engine_tag, check_privacy_tag)
from repro.core.strategies import base as strat_base

AUTO_CHUNK_ROUNDS = 32      # scan compiles its body once, so chunks are cheap


# ---------------------------------------------------------------------------
# Chunking + compile/timing machinery
# ---------------------------------------------------------------------------


def chunk_plan(rounds: int, chunk_rounds: Optional[int] = None,
               ckpt_every: Optional[int] = None,
               start: int = 0) -> List[int]:
    """Split rounds ``[start, rounds)`` into scan-chunk lengths.

    With checkpointing, a chunk boundary lands right after every
    checkpoint round (``r % ckpt_every == 0``) so the recorder can
    materialize the global model there — mid-chunk states never exist
    on the host.  A resumed run passes ``start`` (the round after its
    checkpoint) and the grid stays aligned because the boundary rule is
    a function of the *absolute* round index.
    """
    chunk = max(1, chunk_rounds or min(max(rounds - start, 1),
                                       AUTO_CHUNK_ROUNDS))
    plan, r = [], start
    while r < rounds:
        kc = min(chunk, rounds - r)
        if ckpt_every:
            next_ckpt = r + (-r) % ckpt_every      # first ckpt round ≥ r
            if next_ckpt < rounds:
                kc = min(kc, next_ckpt + 1 - r)
        plan.append(kc)
        r += kc
    return plan


class _ChunkRunner:
    """Compile-once-per-chunk-shape executor with donated carry buffers.

    ``fn(carry, xs) -> (carry, ys)`` is AOT-lowered and compiled the
    first time each chunk length appears, under the ``repro.chunk.lower``
    and ``repro.chunk.compile`` spans, whose sum is ``compile_s``; round
    0's ``step_s`` never absorbs it.  The carry (fl_state + engine
    buffers) is donated, so K rounds run without an extra resident copy
    of the federation's parameters; the caller must never touch a carry
    it has already passed in.
    """

    def __init__(self, fn: Callable):
        self.fn = fn
        self.compile_s = 0.0
        self._cache: Dict[int, Any] = {}

    def run(self, kc: int, carry, xs):
        """Execute one chunk; returns ``(carry', ys, exec_seconds)``."""
        compiled = self._cache.get(kc)
        if compiled is None:
            with telemetry.span("repro.chunk.lower", rounds=kc) as lower:
                lowered = jax.jit(self.fn, donate_argnums=0).lower(carry, xs)
            with telemetry.span("repro.chunk.compile", rounds=kc) as comp:
                compiled = lowered.compile()
            self._cache[kc] = compiled
            self.compile_s += lower.seconds + comp.seconds
        with telemetry.span("repro.chunk.execute") as ex:
            carry, ys = compiled(carry, xs)
            jax.block_until_ready((carry, ys))
        return carry, ys, ex.seconds


def _nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _pairings(masks: np.ndarray, seed: int):
    """Host gossip pairings for every round, consuming the pairing RNG in
    the exact order the retired per-round loop did (parity)."""
    from repro.core.gossip import pair_sites
    rng = np.random.default_rng(seed)
    ps, rs = [], []
    for r in range(masks.shape[0]):
        p, rv, _ = pair_sites(masks[r], rng)
        ps.append(p)
        rs.append(rv)
    return np.stack(ps), np.stack(rs)


def _arrival_orders(masks: np.ndarray, seed: int):
    """Buffered arrival permutations, one per round, padded with zeros
    past the active count — same RNG stream as the retired loop."""
    rng = np.random.default_rng(seed + 13)
    rounds, num_sites = masks.shape
    order = np.zeros((rounds, num_sites), np.int32)
    n_act = np.zeros((rounds,), np.int32)
    for r in range(rounds):
        perm = rng.permutation(np.flatnonzero(masks[r])).astype(np.int32)
        order[r, :len(perm)] = perm
        n_act[r] = len(perm)
    return order, n_act


def _chunk_batches(bundle, r0: int, kc: int, local_steps: int, pooled: bool):
    """[Kc, S, K, …] device batches for one chunk: numpy generation per
    round, stacked, ONE host→device transfer per chunk."""
    rows = []
    for r in range(r0, r0 + kc):
        b = bundle.stacked(r, local_steps)
        if pooled:
            b = bundle.pooled_view(b)
        rows.append(b)
    return {k: jnp.asarray(np.stack([row[k] for row in rows]))
            for k in rows[0]}


# ---------------------------------------------------------------------------
# On-device compression (per-leaf chunk geometry mirrors comms.compression)
# ---------------------------------------------------------------------------


def _chunk_geom(n: int, chunkw: int, align: int):
    """(rows, width) of the quantization chunk matrix for an n-element
    leaf — the wire codec's one chunk-geometry rule, so device and wire
    codecs agree on scales and payload bytes by construction."""
    from repro.comms.compression import chunk_geom
    return chunk_geom(n, chunkw, align)


def _to_chunks(x, chunkw: int, align: int):
    """[S, …] leaf → ([S, rows, c] fp32 chunk matrix, flat size n)."""
    s = x.shape[0]
    n = int(np.prod(x.shape[1:], dtype=np.int64))
    rows, c = _chunk_geom(n, chunkw, align)
    flat = x.reshape(s, n).astype(jnp.float32)
    if rows * c != n:
        flat = jnp.pad(flat, ((0, 0), (0, rows * c - n)))
    return flat.reshape(s, rows, c), n


def _from_chunks(mat, shape, n: int):
    """[…, rows, c] → […, *shape] (drop the zero padding)."""
    lead = mat.shape[:-2]
    return mat.reshape(lead + (-1,))[..., :n].reshape(lead + tuple(shape))


def _qdq_tree(u, chunkw: int, align: int, codec_name: str):
    """Traced quantize→dequantize of a stacked [S, …] pytree with the
    wire codec's per-leaf chunk geometry (pure jnp — bit-identical to
    the numpy codec on CPU).

    Leaves sharing a chunk width are batched into ONE [S, ΣR, c] call:
    chunks never cross leaf boundaries (every leaf is padded to whole
    rows first), so the grouped math is element-identical to per-leaf
    encoding while cutting the op count from O(leaves) to O(widths).
    """
    from repro.kernels.quantize import (quantize_dequantize_fp8_ref,
                                        quantize_dequantize_ref)
    qdq = (quantize_dequantize_ref if codec_name == "int8"
           else quantize_dequantize_fp8_ref)
    leaves, treedef = jax.tree.flatten(u)
    groups: Dict[int, List[int]] = {}
    chunked = []
    for i, x in enumerate(leaves):
        mat, n = _to_chunks(x, chunkw, align)
        chunked.append((mat, n))
        groups.setdefault(mat.shape[-1], []).append(i)
    out: List[Any] = [None] * len(leaves)
    for c, idxs in groups.items():
        mats = [chunked[i][0] for i in idxs]
        deq = qdq(jnp.concatenate(mats, axis=1))
        r0 = 0
        for i, mat in zip(idxs, mats):
            rows = mat.shape[1]
            out[i] = _from_chunks(deq[:, r0:r0 + rows], leaves[i].shape[1:],
                                  chunked[i][1])
            r0 += rows
    return jax.tree.unflatten(treedef, out)


def _topk_tree(u, fraction: float):
    """Traced exact-k magnitude sparsification of a stacked [S, …] pytree
    — the ``topk-fixed`` codec's device twin.  ``k`` per leaf is the same
    ``ceil(fraction · n)`` the wire codec uses, a *static* function of
    the leaf shape, so the scan body stays fixed-shape (the reason the
    original data-shaped ``topk-sparse`` path could not compile)."""
    def one(x):
        s = x.shape[0]
        flat = x.reshape(s, -1).astype(jnp.float32)
        n = flat.shape[1]
        k = max(1, int(np.ceil(fraction * n)))
        if k >= n:
            return flat.reshape(x.shape)
        _, idx = jax.lax.top_k(jnp.abs(flat), k)            # [S, k]
        rows = jnp.arange(s)[:, None]
        kept = jnp.zeros_like(flat).at[rows, idx].set(
            jnp.take_along_axis(flat, idx, axis=1))
        return kept.reshape(x.shape)
    return jax.tree.map(one, u)


def _topk_nbytes(params_stacked, fraction: float) -> int:
    """Wire payload bytes of ONE ``topk-fixed`` upload: a uint32 index +
    fp32 value per kept entry — matches ``tree_payload_nbytes`` over the
    host codec's ``QuantizedTensor``s."""
    total = 0
    for x in jax.tree.leaves(params_stacked):
        n = int(np.prod(x.shape[1:], dtype=np.int64))
        total += 8 * max(1, int(np.ceil(fraction * n)))
    return total


def _compressed_fold(u, w, codec_name: str, chunkw: int, align: int,
                     accel: bool, engine, fold_tree=None, dense=None,
                     fraction: float = 0.1):
    """One round's simulated server step, fully on device: quantize→
    dequantize (or top-k sparsify) every site's upload ``u`` and fold
    Eq. 1 at weights ``w``.  Returns ``(global_delta_tree,
    residual_tree)`` with ``residual = u − deQ(Q(u))``.

    ``fold_tree`` overrides the flat reduction (the pods topology folds
    per-pod partials first); ``dense`` is a traced bool that bypasses the
    codec for the round (the top-k sparsifier's dense bootstrap — it
    must not decimate the one full-model upload of a run).

    On TPU/GPU the int8 path runs the Pallas quantize kernel and the
    fused ``fedagg_dequant`` dequantize+fold, so the dense fp32 per-site
    models never materialize off-chip; on CPU (and for fp8/top-k) the
    jnp twin folds through the ``AggregationEngine``'s padded [S, N]
    buffer.
    """
    if accel and codec_name == "int8" and fold_tree is None:
        from repro.kernels import ops
        leaves, treedef = jax.tree.flatten(u)
        g_leaves, r_leaves = [], []
        for x in leaves:
            mat, n = _to_chunks(x, chunkw, align)
            s, rows, c = mat.shape
            with jax.named_scope("quantize"):
                q, sc = ops.quantize_int8(mat.reshape(s * rows, c))
            g, res = ops.fedagg_dequant(q.reshape(s, rows, c),
                                        sc.reshape(s, rows), mat, w)
            g_leaves.append(_from_chunks(g[None], x.shape[1:], n)[0])
            r_leaves.append(_from_chunks(res, x.shape[1:], n))
        return (jax.tree.unflatten(treedef, g_leaves),
                jax.tree.unflatten(treedef, r_leaves))
    with jax.named_scope("quantize"):
        if codec_name == "topk-fixed":
            deq = _topk_tree(u, fraction)
        else:
            deq = _qdq_tree(u, chunkw, align, codec_name)
    if dense is not None:
        # `dense` is either a per-round scalar (the top-k sparsifier's
        # round-0 bootstrap) or a per-site [S] mask (bidirectional
        # compression: each site bootstraps on ITS OWN rejoin schedule)
        def _sel(full, q):
            d = dense
            if getattr(d, "ndim", 0) == 1:
                d = d.reshape((-1,) + (1,) * (full.ndim - 1))
            return jnp.where(d, full.astype(jnp.float32), q)
        deq = jax.tree.map(_sel, u, deq)
    if fold_tree is not None:
        gdelta = fold_tree(deq)
    else:
        flat, layout = engine.flatten(deq)
        gdelta = engine.unflatten(engine.reduce_flat(flat, w), layout)
    return gdelta, jax.tree.map(jnp.subtract, u, deq)


def _encoded_nbytes(params_stacked, chunkw: int, align: int) -> int:
    """Wire payload bytes of ONE quantized upload under the per-leaf
    chunk layout (1-byte values + fp32 per-chunk scales) — matches
    ``tree_payload_nbytes`` over the host codec's ``QuantizedTensor``s."""
    total = 0
    for x in jax.tree.leaves(params_stacked):
        n = int(np.prod(x.shape[1:], dtype=np.int64))
        rows, c = _chunk_geom(n, chunkw, align)
        total += rows * c + rows * 4
    return total


def _down_install_tree(gref, down_ref, codec_name: str, chunkw: int,
                       align: int, accel: bool, fraction: float):
    """Traced per-site compressed install: each site's new model is its
    held download reference plus the quantized (or top-k sparsified)
    delta of the fresh global against that reference — the device twin
    of ``DownlinkCompressor.encode`` + ``decode_download``.  Feeding the
    result back as the next round's reference IS the downlink error-
    feedback recurrence (``held ← held + deQ(Q(g − held))``), so
    quantization errors telescope across rounds.  On accelerators the
    int8 path runs the fused ``dequant_install`` Pallas kernel, so the
    dense per-site deltas never materialize in HBM."""
    delta = jax.tree.map(lambda g, h: g[None] - h, gref, down_ref)
    if accel and codec_name == "int8":
        from repro.kernels import ops

        def one(d, h):
            mat, n = _to_chunks(d, chunkw, align)
            s, rows, c = mat.shape
            with jax.named_scope("quantize"):
                q, sc = ops.quantize_int8(mat.reshape(s * rows, c))
            hmat, _ = _to_chunks(h, chunkw, align)
            with jax.named_scope("dequant_install"):
                inst = ops.dequant_install(q.reshape(s, rows, c),
                                           sc.reshape(s, rows), hmat)
            return _from_chunks(inst, d.shape[1:], n)
        return jax.tree.map(one, delta, down_ref)
    with jax.named_scope("quantize"):
        if codec_name == "topk-fixed":
            qd = _topk_tree(delta, fraction)
        else:
            qd = _qdq_tree(delta, chunkw, align, codec_name)
    with jax.named_scope("dequant_install"):
        return jax.tree.map(jnp.add, down_ref, qd)


def _bootstrap_masks(masks: np.ndarray, keep: int) -> np.ndarray:
    """[rounds, S] — which (round, site) exchanges bootstrap dense under
    bidirectional compression: the site's previous participation is
    ``keep`` or more rounds back (its upload reference left the server's
    ``keep_globals`` window and its download reference was evicted on
    the same clock), or it never participated.  A pure function of the
    participation masks, so a resumed run replays the identical
    schedule."""
    rounds, s = masks.shape
    last = np.full(s, -keep, np.int64)          # "never": forces bootstrap
    boot = np.zeros((rounds, s), bool)
    for r in range(rounds):
        boot[r] = masks[r] & (r - last >= keep)
        last[masks[r]] = r
    return boot


def _accel() -> bool:
    from repro.kernels.ops import _default_interpret
    return not _default_interpret()


# ---------------------------------------------------------------------------
# Sync rounds (every strategy) — one scan per chunk
# ---------------------------------------------------------------------------


def _run_sync_scan(job, bundle, scheduler, rounds: int,
                   resume_round: Optional[int] = None) -> JobResult:
    ctx = job.context(bundle)
    strategy = strat_base.get_strategy(job.strategy)
    num_sites = ctx.fed.num_sites
    state = F.init_fl_state(ctx, bundle.init_fn, jax.random.PRNGKey(job.seed))
    # a pods topology changes nothing here beyond the strategy's
    # post_exchange hook: aggregate_round segment-reduces the padded
    # [S, N] buffer by pod id inside the same scanned body
    fl_round = F.build_fl_round(ctx)
    needs_val = strategy.needs_val_batch
    needs_pair = strategy.needs_pairing
    pooled = job.strategy == "pooled"
    device_data = bool(job.device_data)

    masks = job.masks(rounds)
    # client sampling: the [rounds, S] 1/π Eq. 1 factor rides the chunk
    # xs only when sampling thins participation — unsampled runs keep a
    # bit-identical scan body and carry
    wscale = job.weight_scale(rounds) if job.sampled else None
    if needs_pair and not device_data:
        partner, is_recv = _pairings(masks, job.seed)
    else:
        partner = np.broadcast_to(np.arange(num_sites), masks.shape).copy()
        is_recv = np.zeros(masks.shape, bool)

    def add_val_batches(ri, b):
        if needs_val:
            ri["dcml_batch"] = jax.tree.map(lambda x: x[:, 0], b)
            ri["val_batch"] = jax.tree.map(lambda x: x[:, -1], b)
        return ri

    if device_data:
        from repro.core.dropout import availability_step_traced
        data_key = jax.random.fold_in(jax.random.PRNGKey(job.seed), 7)

        def chunk_fn(carry, xs):
            def body(c, r):
                st, active = c
                k_av, k_pair, k_data = jax.random.split(
                    jax.random.fold_in(data_key, r), 3)
                if job.max_dropout:
                    active = availability_step_traced(k_av, active,
                                                      job.max_dropout)
                ri = F.make_round_inputs_traced(ctx, k_pair, active)
                b = bundle.traced_stacked(k_data, job.local_steps,
                                          job.task.batch)
                st, metrics = fl_round(st, b, add_val_batches(ri, b))
                ys = {"loss": metrics["loss"], "active": active,
                      "partner": ri["partner"],
                      "is_receiver": ri["is_receiver"]}
                return (st, active), ys
            return jax.lax.scan(body, carry, xs)

        carry = (state, jnp.ones((num_sites,), bool))
    else:
        def chunk_fn(carry, xs):
            def body(st, x):
                b = x["batches"]
                ri = {"active": x["active"], "partner": x["partner"],
                      "is_receiver": x["is_receiver"]}
                if "wscale" in x:
                    ri["weight_scale"] = x["wscale"]
                st, metrics = fl_round(st, b, add_val_batches(ri, b))
                return st, {"loss": metrics["loss"]}
            return jax.lax.scan(body, carry, xs)

        carry = state

    runner = _ChunkRunner(chunk_fn)
    recorder = job.recorder(rounds, num_sites)
    start_round = 0
    if resume_round is not None:
        lmeta = recorder.store.meta("driver_state", resume_round)
        check_engine_tag(lmeta, "sync-scan")
        check_privacy_tag(lmeta, job.dp_tag())
        loaded, _ = recorder.store.load(
            "driver_state", resume_round, jax.tree.map(np.asarray, carry))
        carry = jax.tree.map(jnp.asarray, loaded)
        state = carry[0] if device_data else carry
        start_round = resume_round + 1
    masks_seen: List[np.ndarray] = []
    r0 = start_round
    plan = chunk_plan(rounds, job.chunk_rounds,
                      job.ckpt_every if recorder.store else None,
                      start=start_round)
    for kc in plan:
        with recorder.chunk(r0, kc):
            with telemetry.span("repro.chunk.inputs") as inputs:
                if device_data:
                    xs = jnp.arange(r0, r0 + kc)
                else:
                    xs = {"batches": _chunk_batches(bundle, r0, kc,
                                                    job.local_steps, pooled),
                          "active": jnp.asarray(masks[r0:r0 + kc]),
                          "partner": jnp.asarray(partner[r0:r0 + kc]),
                          "is_receiver": jnp.asarray(is_recv[r0:r0 + kc])}
                    if wscale is not None:
                        xs["wscale"] = jnp.asarray(wscale[r0:r0 + kc])
                inputs.set(bytes=_nbytes(xs))
            carry, ys, exec_s = runner.run(kc, carry, xs)
            state = carry[0] if device_data else carry
            with telemetry.span("repro.chunk.record"):
                losses = np.asarray(ys["loss"])
                if device_data:
                    rows = np.asarray(ys["active"])
                    p_rows, r_rows = np.asarray(ys["partner"]), np.asarray(
                        ys["is_receiver"])
                    masks_seen.append(rows)
                else:
                    rows = masks[r0:r0 + kc]
                    p_rows, r_rows = partner[r0:r0 + kc], is_recv[r0:r0 + kc]
                for i in range(kc):
                    extra = {"step_s": exec_s / kc}
                    if needs_pair:
                        extra["partner"] = [int(v) for v in p_rows[i]]
                        extra["is_receiver"] = [bool(v) for v in r_rows[i]]
                    recorder.record(
                        r0 + i, losses[i], rows[i],
                        global_fn=(lambda st=state: F.global_model(st, ctx))
                        if i == kc - 1 else None,
                        extra=extra)
                recorder.save_state(r0 + kc - 1,
                                    lambda: jax.tree.map(np.asarray, carry),
                                    meta={"engine": "sync-scan",
                                          "dp": job.dp_tag()})
        r0 += kc
    all_masks = (np.concatenate(masks_seen) if masks_seen
                 else masks[start_round:])
    comm = None
    if job.strategy in ("fedavg", "fedprox"):
        nbytes = per_site_nbytes(state["params"])
        if ctx.topology.is_pods:
            from repro.core.topology import simulated_pods_comm
            comm = simulated_pods_comm(ctx.topology, all_masks, nbytes)
        else:
            uploads = int(all_masks.sum())
            comm = {"upload_bytes": uploads * nbytes,
                    "download_bytes": uploads * nbytes,
                    "total_bytes": 2 * uploads * nbytes,
                    "upload_count": uploads, "download_count": uploads,
                    "compression": "none", "down_compression": "none",
                    "simulated": True}
    return recorder.result(F.global_model(state, ctx), transport="stacked",
                           scheduler=scheduler.name, state=state, comm=comm,
                           compile_s=runner.compile_s,
                           resumed_from=resume_round,
                           privacy=job.privacy_report(rounds))


# ---------------------------------------------------------------------------
# Compressed sync rounds (int8/fp8 fedavg) — on-device codec + fold
# ---------------------------------------------------------------------------


def _run_compressed_scan(job, bundle, scheduler, rounds: int, codec,
                         resume_round: Optional[int] = None,
                         down_codec=None) -> JobResult:
    """Compressed sync rounds on device.  Local training runs under the
    strategy's *site half* — ``individual`` for FedAvg, ``fedprox-local``
    for FedProx (the Eq. 2 proximal pull, re-anchored to every broadcast
    global inside the scan) — and the simulated server fold goes through
    the codec's device twin: int8/fp8 quantize→dequantize or the
    ``topk-fixed`` exact-k sparsifier (dense on the bootstrap round).  A
    pods topology swaps the flat fold for the two-tier segment-reduce.

    With ``down_codec`` (bidirectional compression) the broadcast rides
    the codec seam too: per-site download references become additional
    ``[S, …]`` scan carry, every install is a quantized delta against
    that site's held reference (``_down_install_tree`` — the fused
    ``dequant_install`` kernel on accelerators), uploads anchor to the
    site's OWN install instead of the shared global, and the fold becomes
    ``g = Σ wₛ(anchorₛ + deQ(uₛ))`` — exactly the socket server's
    per-site decode.  Sites whose reference left the ``keep_globals``
    window bootstrap dense both ways on a host-precomputed
    ``_bootstrap_masks`` schedule.  Engines anchor FedProx's Eq. 2 at
    the exact global (the vmapped round body broadcasts ONE anchor);
    socket sites anchor at their decoded install — the difference is the
    downlink quantization error, which the EF recurrence telescopes."""
    local_strategy = ("fedprox-local" if job.strategy == "fedprox"
                      else "individual")
    prox = local_strategy == "fedprox-local"
    ctx = job.context(bundle, strategy=local_strategy)  # local-only rounds
    num_sites = ctx.fed.num_sites
    state = F.init_fl_state(ctx, bundle.init_fn, jax.random.PRNGKey(job.seed))
    fl_round = F.build_fl_round(ctx)
    masks = job.masks(rounds)
    wscale = job.weight_scale(rounds) if job.sampled else None
    case_w = jnp.asarray(np.asarray(job.federation().case_weights()),
                         jnp.float32)
    engine = get_engine()
    accel = _accel()
    chunkw = int(getattr(codec, "chunk", 1024))
    align = 128 if (accel and codec.name == "int8") else 1
    fraction = float(getattr(codec, "fraction", 0.1))
    topk = codec.name == "topk-fixed"
    up = codec.name != "none"
    down = down_codec is not None and down_codec.name != "none"
    d_chunkw = int(getattr(down_codec, "chunk", 1024)) if down else chunkw
    d_align = 128 if (accel and down and down_codec.name == "int8") else 1
    d_fraction = (float(getattr(down_codec, "fraction", 0.1)) if down
                  else fraction)
    error_feedback = bool(job.error_feedback)
    identity = np.arange(num_sites)
    no_recv = np.zeros(num_sites, bool)
    topo = job.topo
    pod_ids = jnp.asarray(topo.pod_of(num_sites)) if topo.is_pods else None

    # the init model is "reference zero": round 0's delta against zeros IS
    # the dense bootstrap upload the wire codec would send
    reference = jax.tree.map(lambda x: jnp.zeros(x.shape[1:], jnp.float32),
                             state["params"])
    residual = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                            state["params"])

    def fold_plain(tree, w, active, scale):
        """Σ wₛ · treeₛ over a stacked [S, …] tree — the flat Eq. 1
        reduce, or the two-tier segment-reduce under a pods topology."""
        flat, layout = engine.flatten(tree)
        if pod_ids is not None:
            g = engine.reduce_pods_flat(flat, case_w, active, pod_ids,
                                        topo.num_pods, topo.intra,
                                        topo.inter, scale=scale)
        else:
            g = engine.reduce_flat(flat, w)
        return engine.unflatten(g, layout)

    def chunk_fn(carry, xs):
        def body(c, x):
            st, ref, res = c
            active = x["active"]
            st, metrics = fl_round(st, x["batches"],
                                   {"active": active, "partner": identity,
                                    "is_receiver": no_recv})
            # delta vs last broadcast global, plus the carried EF residual
            u = jax.tree.map(
                lambda p, g, e: p.astype(jnp.float32) - g[None] + e,
                st["params"], ref, res)
            scale = x.get("wscale")
            w = normalized_weights(case_w, active, scale)
            fold_tree = None
            if pod_ids is not None:
                def fold_tree(deq, active=active, scale=scale):
                    return fold_plain(deq, None, active, scale)
            with jax.named_scope("fold"):
                gdelta, new_res = _compressed_fold(
                    u, w, codec.name, chunkw, align, accel, engine,
                    fold_tree=fold_tree,
                    dense=x["bootstrap"] if topk else None,
                    fraction=fraction)
            if error_feedback:
                res = stacking.where_site(active, new_res, res)
            ref = jax.tree.map(jnp.add, ref, gdelta)
            bcast = jax.tree.map(
                lambda g, p: jnp.broadcast_to(g[None], p.shape).astype(p.dtype),
                ref, st["params"])
            st = {**st, "params": stacking.where_site(active, bcast,
                                                      st["params"])}
            if prox:            # next round's proximal anchor = this global
                st = {**st, "strategy": {"global": ref}}
            return (st, ref, res), {"loss": metrics["loss"]}
        return jax.lax.scan(body, carry, xs)

    def chunk_fn_bidir(carry, xs):
        def body(c, x):
            st, gref, dref, res = c
            active = x["active"]
            boot = x["bootstrap"]                       # [S] bool
            st, metrics = fl_round(st, x["batches"],
                                   {"active": active, "partner": identity,
                                    "is_receiver": no_recv})
            scale = x.get("wscale")
            w = normalized_weights(case_w, active, scale)

            def rowsel(a, b):
                # per-site select on the stacked axis
                return jax.tree.map(
                    lambda aa, bb: jnp.where(
                        boot.reshape((-1,) + (1,) * (aa.ndim - 1)), aa, bb),
                    a, b)
            # upload anchor: the site's OWN held install; a site whose
            # reference left the server window uploads dense (anchor 0)
            anchor = rowsel(jax.tree.map(jnp.zeros_like, dref), dref)
            if up:
                u = jax.tree.map(
                    lambda p, a, e: p.astype(jnp.float32) - a + e,
                    st["params"], anchor, res)
                fold_tree = None
                if pod_ids is not None:
                    def fold_tree(deq, active=active, scale=scale):
                        return fold_plain(deq, None, active, scale)
                with jax.named_scope("fold"):
                    gdelta, new_res = _compressed_fold(
                        u, w, codec.name, chunkw, align, accel, engine,
                        fold_tree=fold_tree, dense=boot if topk else None,
                        fraction=fraction)
                if error_feedback:
                    res = stacking.where_site(active, new_res, res)
                # per-site decode: g = Σ wₛ(anchorₛ + deQ(uₛ)) — the
                # anchors differ per site, so the fold carries them too
                with jax.named_scope("fold"):
                    gref = jax.tree.map(
                        jnp.add, fold_plain(anchor, w, active, scale), gdelta)
            else:
                # down-only compression: uploads ride dense fp32
                with jax.named_scope("fold"):
                    gref = fold_plain(
                        jax.tree.map(lambda p: p.astype(jnp.float32),
                                     st["params"]), w, active, scale)
            # downlink: quantized delta against each site's held
            # reference; bootstrap rows (new/evicted) get the dense global
            inst = _down_install_tree(gref, dref, down_codec.name, d_chunkw,
                                      d_align, accel, d_fraction)
            inst = rowsel(jax.tree.map(
                lambda g, q: jnp.broadcast_to(g[None], q.shape), gref, inst),
                inst)
            dref = stacking.where_site(active, inst, dref)
            bcast = jax.tree.map(lambda i_, p: i_.astype(p.dtype),
                                 inst, st["params"])
            st = {**st, "params": stacking.where_site(active, bcast,
                                                      st["params"])}
            if prox:        # engines broadcast ONE Eq. 2 anchor (exact
                            # global); socket sites anchor at their install
                st = {**st, "strategy": {"global": gref}}
            return (st, gref, dref, res), {"loss": metrics["loss"]}
        return jax.lax.scan(body, carry, xs)

    engine_tag = "compressed-scan-bidir" if down else "compressed-scan"
    runner = _ChunkRunner(chunk_fn_bidir if down else chunk_fn)
    recorder = job.recorder(rounds, num_sites)
    dense_nbytes = per_site_nbytes(state["params"])
    enc_nbytes = (dense_nbytes if not up
                  else _topk_nbytes(state["params"], fraction) if topk
                  else _encoded_nbytes(state["params"], chunkw, align))
    # host-precomputed per-round wire bytes — bit-identical to the loop
    # twin's tree_payload_nbytes counters.  Dense bootstrap uploads still
    # ride the codec (quantized dense) except under top-k, whose
    # dense_bootstrap rule ships raw fp32; dense bootstrap downloads
    # always ship raw fp32 (the DownlinkCompressor's "none" reply).
    if down:
        boot_mask = _bootstrap_masks(masks, KEEP_GLOBALS_DEFAULT)
        down_enc = (_topk_nbytes(state["params"], d_fraction)
                    if down_codec.name == "topk-fixed"
                    else _encoded_nbytes(state["params"], d_chunkw, d_align))
        per_up = (np.where(boot_mask, dense_nbytes, enc_nbytes) if topk
                  else np.full(masks.shape, enc_nbytes, np.int64))
        round_up_bytes = np.where(masks, per_up, 0).sum(axis=1)
        round_down_bytes = np.where(
            masks, np.where(boot_mask, dense_nbytes, down_enc), 0).sum(axis=1)
    else:
        # the wire codec's dense_bootstrap rule: round 0 (no reference
        # global yet) rides dense; sparsity starts once deltas exist
        round_up_bytes = np.asarray(
            [int(masks[r].sum()) * (dense_nbytes if (topk and r == 0)
                                    else enc_nbytes)
             for r in range(rounds)], np.int64)
        round_down_bytes = masks.sum(axis=1).astype(np.int64) * dense_nbytes
    if down:
        down_ref0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                 state["params"])
        carry = (state, reference, down_ref0, residual)
    else:
        carry = (state, reference, residual)
    start_round = 0
    if resume_round is not None:
        lmeta = recorder.store.meta("driver_state", resume_round)
        check_engine_tag(lmeta, engine_tag)
        check_privacy_tag(lmeta, job.dp_tag())
        loaded, _ = recorder.store.load(
            "driver_state", resume_round, jax.tree.map(np.asarray, carry))
        carry = jax.tree.map(jnp.asarray, loaded)
        start_round = resume_round + 1
    r0 = start_round
    for kc in chunk_plan(rounds, job.chunk_rounds,
                         job.ckpt_every if recorder.store else None,
                         start=start_round):
        with recorder.chunk(r0, kc):
            with telemetry.span("repro.chunk.inputs") as inputs:
                xs = {"batches": _chunk_batches(bundle, r0, kc,
                                                job.local_steps, False),
                      "active": jnp.asarray(masks[r0:r0 + kc])}
                if wscale is not None:
                    xs["wscale"] = jnp.asarray(wscale[r0:r0 + kc])
                if down:
                    xs["bootstrap"] = jnp.asarray(boot_mask[r0:r0 + kc])
                elif topk:
                    xs["bootstrap"] = jnp.asarray(
                        [r == 0 for r in range(r0, r0 + kc)])
                inputs.set(bytes=_nbytes(xs))
            carry, ys, exec_s = runner.run(kc, carry, xs)
            with telemetry.span("repro.chunk.record"):
                losses = np.asarray(ys["loss"])
                for i in range(kc):
                    extra = {"step_s": exec_s / kc,
                             "upload_bytes": int(round_up_bytes[r0 + i])}
                    if down:
                        extra["download_bytes"] = int(
                            round_down_bytes[r0 + i])
                    recorder.record(
                        r0 + i, losses[i], masks[r0 + i],
                        global_fn=(lambda c=carry: c[1]) if i == kc - 1
                        else None,
                        extra=extra)
                recorder.save_state(r0 + kc - 1,
                                    lambda: jax.tree.map(np.asarray, carry),
                                    meta={"engine": engine_tag,
                                          "dp": job.dp_tag()})
        r0 += kc
    state, reference = carry[0], carry[1]
    uploads = int(masks[start_round:].sum())
    upload_bytes = int(round_up_bytes[start_round:].sum())
    download_bytes = int(round_down_bytes[start_round:].sum())
    comm = {"upload_bytes": upload_bytes,
            "upload_raw_bytes": uploads * dense_nbytes,
            "download_bytes": download_bytes,
            "download_raw_bytes": uploads * dense_nbytes,
            "total_bytes": upload_bytes + download_bytes,
            "upload_count": uploads, "download_count": uploads,
            "compression": codec.name,
            "down_compression": down_codec.name if down else "none",
            "simulated": True}
    if topo.is_pods:
        from repro.core.topology import simulated_pods_comm
        comm.update(simulated_pods_comm(
            topo, masks[start_round:], dense_nbytes,
            intra_upload_bytes=upload_bytes,
            intra_download_bytes=download_bytes if down else None,
            compression=codec.name,
            down_compression=down_codec.name if down else "none"))
    return recorder.result(reference, transport="stacked",
                           scheduler=scheduler.name, state=state, comm=comm,
                           compile_s=runner.compile_s,
                           resumed_from=resume_round,
                           privacy=job.privacy_report(rounds))


# ---------------------------------------------------------------------------
# Buffered (FedBuff) rounds — the arrival loop as device state
# ---------------------------------------------------------------------------


def _run_buffered_scan(job, bundle, scheduler, rounds: int, codec,
                       resume_round: Optional[int] = None) -> JobResult:
    compress = codec.name != "none"
    ctx = job.context(bundle, strategy="individual")
    num_sites = ctx.fed.num_sites
    state = F.init_fl_state(ctx, bundle.init_fn, jax.random.PRNGKey(job.seed))
    fl_round = F.build_fl_round(ctx)
    masks = job.masks(rounds)
    order, n_act = _arrival_orders(masks, job.seed)
    case_w = jnp.asarray(np.asarray(job.federation().case_weights()),
                         jnp.float32)
    engine = get_engine()
    flat0, layout = engine.flatten(state["params"])
    n = layout.n
    g0 = engine.reduce_flat(flat0, case_w / jnp.sum(case_w))
    identity = np.arange(num_sites)
    no_recv = np.zeros(num_sites, bool)
    buffer_k = int(scheduler.buffer_k)
    alpha = float(scheduler.alpha)
    max_st = int(scheduler.max_staleness)
    keep = KEEP_GLOBALS_DEFAULT
    error_feedback = bool(job.error_feedback)
    chunkw = int(getattr(codec, "chunk", 1024))
    rows_f, c_f = _chunk_geom(n, chunkw, 1)
    if compress:
        from repro.kernels.quantize import (quantize_dequantize_fp8_ref,
                                            quantize_dequantize_ref)
        qdq = (quantize_dequantize_ref if codec.name == "int8"
               else quantize_dequantize_fp8_ref)

        def qdq_flat(u):
            mat = jnp.pad(u, (0, rows_f * c_f - n)).reshape(rows_f, c_f)
            return qdq(mat).reshape(-1)[:n]

    carry = {"state": state, "acc": jnp.zeros((n,), jnp.float32),
             "accw": jnp.zeros((), jnp.float32),
             "count": jnp.zeros((), jnp.int32),
             "version": jnp.zeros((), jnp.int32),
             "base": jnp.zeros((num_sites,), jnp.int32), "gflat": g0}
    if compress:
        # version → decode reference, as a bounded on-device ring (the
        # AggregationServer's keep_globals window); slot 0 = init model
        carry["ring"] = jnp.zeros((keep, n), jnp.float32).at[0].set(g0)
        carry["residual"] = jnp.zeros((num_sites, n), jnp.float32)

    def chunk_fn(carry, xs):
        def body(c, x):
            st, metrics = fl_round(c["state"], x["batches"],
                                   {"active": x["active"],
                                    "partner": identity,
                                    "is_receiver": no_recv})
            pflat = engine.flatten(st["params"])[0]
            ord_r, na = x["order"], x["n_act"]
            kmin = jnp.minimum(buffer_k, jnp.maximum(na, 1))

            def arrival(j, a):
                (pflat, acc, accw, count, version, base, gflat, ring,
                 residual, uploaded, folds) = a
                site = ord_r[j]
                valid = j < na
                tau = version - base[site]
                ok = (tau >= 0) & (tau <= max_st)
                admit = valid & ok
                reject = valid & ~ok
                disc = (1.0 + jnp.clip(tau, 0, max_st).astype(jnp.float32)
                        ) ** (-alpha)
                upload = pflat[site]
                if compress:
                    ref = ring[base[site] % keep]
                    u = upload - ref + residual[site]
                    deq = qdq_flat(u)
                    if error_feedback:
                        residual = residual.at[site].set(
                            jnp.where(admit, u - deq, residual[site]))
                    decoded = deq + ref
                else:
                    decoded = upload
                w = case_w[site] * disc * admit
                acc = acc + w * decoded
                accw = accw + w
                count = count + admit
                folds = folds + admit
                uploaded = uploaded.at[site].set(uploaded[site] | admit)
                # too stale: resync to the current global, no contribution
                pflat = pflat.at[site].set(jnp.where(reject, gflat,
                                                     pflat[site]))
                base = base.at[site].set(jnp.where(reject, version,
                                                   base[site]))
                fire = admit & (count >= kmin)
                newg = acc / jnp.maximum(accw, jnp.float32(1e-12))
                gflat = jnp.where(fire, newg, gflat)
                version = version + fire
                if compress:
                    slot = version % keep
                    ring = ring.at[slot].set(jnp.where(fire, newg,
                                                       ring[slot]))
                acc = jnp.where(fire, jnp.zeros_like(acc), acc)
                accw = jnp.where(fire, jnp.zeros_like(accw), accw)
                count = jnp.where(fire, jnp.zeros_like(count), count)
                return (pflat, acc, accw, count, version, base, gflat, ring,
                        residual, uploaded, folds)

            a0 = (pflat, c["acc"], c["accw"], c["count"], c["version"],
                  c["base"], c["gflat"],
                  c.get("ring", jnp.zeros((), jnp.float32)),
                  c.get("residual", jnp.zeros((), jnp.float32)),
                  jnp.zeros((num_sites,), bool), jnp.zeros((), jnp.int32))
            with jax.named_scope("fold"):
                (pflat, acc, accw, count, version, base, gflat, ring,
                 residual, uploaded, folds) = jax.lax.fori_loop(
                    0, num_sites, arrival, a0)
            # uploaders pull the latest global and re-anchor
            pflat = jnp.where(uploaded[:, None], gflat[None, :], pflat)
            base = jnp.where(uploaded, version, base)
            st = {**st, "params": engine.unflatten_stacked(pflat, layout)}
            nc = {"state": st, "acc": acc, "accw": accw, "count": count,
                  "version": version, "base": base, "gflat": gflat}
            if compress:
                nc["ring"], nc["residual"] = ring, residual
            return nc, {"loss": metrics["loss"], "version": version,
                        "folds": folds}
        return jax.lax.scan(body, carry, xs)

    runner = _ChunkRunner(chunk_fn)
    recorder = job.recorder(rounds, num_sites)
    start_round = 0
    if resume_round is not None:
        lmeta = recorder.store.meta("driver_state", resume_round)
        check_engine_tag(lmeta, "buffered-scan")
        check_privacy_tag(lmeta, job.dp_tag())
        loaded, _ = recorder.store.load(
            "driver_state", resume_round, jax.tree.map(np.asarray, carry))
        carry = jax.tree.map(jnp.asarray, loaded)
        start_round = resume_round + 1
    total_folds = 0
    r0 = start_round
    for kc in chunk_plan(rounds, job.chunk_rounds,
                         job.ckpt_every if recorder.store else None,
                         start=start_round):
        with recorder.chunk(r0, kc):
            with telemetry.span("repro.chunk.inputs") as inputs:
                xs = {"batches": _chunk_batches(bundle, r0, kc,
                                                job.local_steps, False),
                      "active": jnp.asarray(masks[r0:r0 + kc]),
                      "order": jnp.asarray(order[r0:r0 + kc]),
                      "n_act": jnp.asarray(n_act[r0:r0 + kc])}
                inputs.set(bytes=_nbytes(xs))
            carry, ys, exec_s = runner.run(kc, carry, xs)
            with telemetry.span("repro.chunk.record"):
                losses = np.asarray(ys["loss"])
                versions = np.asarray(ys["version"])
                total_folds += int(np.asarray(ys["folds"]).sum())
                for i in range(kc):
                    recorder.record(
                        r0 + i, losses[i], masks[r0 + i],
                        global_fn=(lambda c=carry: engine.unflatten(
                            c["gflat"], layout)) if i == kc - 1 else None,
                        extra={"version": int(versions[i]),
                               "step_s": exec_s / kc})
                recorder.save_state(r0 + kc - 1,
                                    lambda: jax.tree.map(np.asarray, carry),
                                    meta={"engine": "buffered-scan",
                                          "dp": job.dp_tag()})
        r0 += kc
    state = carry["state"]
    global_params = engine.unflatten(carry["gflat"], layout)
    comm = None
    if compress:
        enc = rows_f * c_f + rows_f * 4          # flat-layout payload bytes
        down_b = total_folds * per_site_nbytes(state["params"])
        comm = {"upload_bytes": total_folds * enc,
                "upload_raw_bytes": total_folds * n * 4,
                "download_bytes": down_b,
                "total_bytes": total_folds * enc + down_b,
                "upload_count": total_folds, "download_count": total_folds,
                "compression": codec.name, "down_compression": "none",
                "simulated": True}
    return recorder.result(global_params, transport="stacked",
                           scheduler=scheduler.name, state=state, comm=comm,
                           compile_s=runner.compile_s,
                           resumed_from=resume_round,
                           privacy=job.privacy_report(rounds))


# ---------------------------------------------------------------------------
# Sharded cross-device engine — the [S, …] site state partitioned over a mesh
# ---------------------------------------------------------------------------


def _pack_participants(participate: np.ndarray, weight: np.ndarray,
                       pod_of: np.ndarray, s_loc: int, num_devices: int):
    """Pack each round's participants into static per-device slots.

    Sites live in contiguous blocks of ``s_loc`` rows per device, so a
    participant never moves between devices: each device trains exactly
    the sampled rows it already owns and only the O(N) fold crosses the
    mesh.  Returns ``(lidx, valid, w, pod, gsite, k_cap)`` where every
    array is [rounds, D, k_cap]; padded slots carry ``lidx == s_loc``
    (out of range — gathers clip to a throwaway row, scatters drop) and
    weight 0.
    """
    rounds = participate.shape[0]
    dev_of = np.arange(participate.shape[1]) // s_loc
    counts = [[int(np.sum(participate[r] & (dev_of == d)))
               for d in range(num_devices)] for r in range(rounds)]
    k_cap = max(1, max(max(c) for c in counts))
    lidx = np.full((rounds, num_devices, k_cap), s_loc, np.int32)
    valid = np.zeros((rounds, num_devices, k_cap), bool)
    w = np.zeros((rounds, num_devices, k_cap), np.float32)
    pod = np.zeros((rounds, num_devices, k_cap), np.int32)
    gsite = np.zeros((rounds, num_devices, k_cap), np.int32)
    for r in range(rounds):
        for d in range(num_devices):
            sites = np.flatnonzero(participate[r] & (dev_of == d))
            k = len(sites)
            lidx[r, d, :k] = sites - d * s_loc
            valid[r, d, :k] = True
            w[r, d, :k] = weight[r, sites]
            pod[r, d, :k] = pod_of[sites]
            gsite[r, d, :k] = sites
    return lidx, valid, w, pod, gsite, k_cap


def execute_sharded(job, bundle, scheduler, codec, rounds: int,
                    resume_round: Optional[int] = None) -> JobResult:
    """Cross-device scale: the stacked simulator with its per-site state
    sharded over a ``("site",)`` device mesh and only the *sampled* rows
    trained each round.

    The dense engines materialize every site every round — [S, …] params
    AND [S, …] batches AND an S-wide vmap — which caps S at what one
    device holds and trains.  Here the persistent per-site state (params
    + the stateful adamw moments, plus the int8 error-feedback residual)
    stays resident as ``shard_map``-partitioned ``[S, …]`` blocks, and a
    round touches exactly the ``participate = sampled ∩ available`` rows
    (``repro.core.sampling``): each device gathers its own participants
    into a static ``[k_cap, …]`` slab, trains them vmapped, folds Eq. 1
    partial sums (Hájek 1/π-scaled weights) through a per-pod
    segment-reduce + ``psum``, scatters the trained rows back and
    broadcasts the new global to the participants only — so a
    10,000-site job at 1% sampling costs ~100 sites of compute and one
    O(N) collective per round.

    Non-participants are frozen (neither train nor see the broadcast):
    exactly ``dropout_scenario="shutdown"`` semantics, hence the gate.
    Sampling schedules, weights and batches are pure functions of
    (seed, round) shared with every other engine, so a full-participation
    sharded run is the dense run (allclose; summation order differs
    across device blocks).
    """
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import make_site_mesh

    if isinstance(scheduler, BufferedScheduler):
        raise ValueError("shard_sites=True runs synchronous rounds only; "
                         "buffered-async scheduling needs the dense engine")
    if job.strategy not in ("fedavg", "fedprox"):
        raise ValueError("shard_sites=True supports the centrally-"
                         "aggregated strategies (fedavg/fedprox), not "
                         f"{job.strategy!r}")
    if codec.name not in ("none", "int8"):
        raise ValueError("shard_sites=True supports compression 'none' or "
                         f"'int8', not {codec.name!r}")
    from repro.comms.compression import resolve_codec
    if resolve_codec(getattr(job, "down_compression", "none")).name != "none":
        raise ValueError("shard_sites=True broadcasts the global through "
                         "the mesh collective, not the download codec; run "
                         "down_compression jobs on the dense engines")
    if job.device_data:
        raise ValueError("shard_sites=True generates only the sampled "
                         "rows' batches host-side; device_data=True would "
                         "regenerate all S on device")
    if job.dp is not None:
        raise ValueError("shard_sites=True does not thread DP-SGD noise "
                         "keys yet; run dp jobs on the dense engines")
    if resume_round is not None:
        raise ValueError("shard_sites=True does not checkpoint its "
                         "sharded carry; resume dense jobs instead")
    thinned = job.sampled or job.max_dropout or job.pod_dropout
    if thinned and job.dropout_scenario != "shutdown":
        raise ValueError(
            "shard_sites=True freezes non-participants entirely (they "
            "neither train nor receive the broadcast), which is the "
            "'shutdown' scenario; run sampled/dropout sharded jobs with "
            "dropout_scenario='shutdown'")

    mesh = make_site_mesh()
    num_devices = int(mesh.devices.size)
    num_sites = job.task.sites
    s_loc = -(-num_sites // num_devices)
    s_pad = s_loc * num_devices

    participate, wscale = job.participation(rounds)
    case_w = np.asarray(job.federation().case_weights(), np.float32)
    topo = job.topo
    if topo.is_pods:
        topo.validate(num_sites)
        num_pods = topo.num_pods
        pod_of = np.asarray(topo.pod_of(num_sites), np.int32)
        intra, inter = topo.intra, topo.inter
    else:
        # the flat fold is the 1-pod special case of the segment-reduce
        num_pods, pod_of = 1, np.zeros(num_sites, np.int32)
        intra, inter = "fedavg", "fedavg"
    base_w = np.ones(num_sites, np.float32) if intra == "uniform" else case_w
    lidx_a, valid_a, w_a, pod_a, gsite_a, k_cap = _pack_participants(
        participate, base_w[None] * wscale, pod_of, s_loc, num_devices)

    quant = codec.name == "int8"
    prox = job.strategy == "fedprox"
    local_strategy = "fedprox-local" if prox else "individual"
    ctx = job.context(bundle, strategy=local_strategy)
    fl_round = F.build_fl_round(ctx)
    engine = get_engine()
    chunkw = int(getattr(codec, "chunk", 1024))
    align = 128 if (_accel() and quant) else 1
    error_feedback = bool(job.error_feedback)
    identity_k = np.arange(k_cap)
    no_recv_k = np.zeros(k_cap, bool)
    steps = job.local_steps

    one = bundle.init_fn(jax.random.PRNGKey(job.seed))
    opt_one = ctx.optimizer.init(one)
    # byte accounting up front: `one`'s buffers may be donated into the
    # carry below (device_put aliases an already-placed array)
    stacked_one = jax.tree.map(lambda x: np.asarray(x)[None], one)
    dense_nbytes = per_site_nbytes(stacked_one)
    row_shard = NamedSharding(mesh, PartitionSpec("site"))
    repl = NamedSharding(mesh, PartitionSpec())

    def bcast_rows(t):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (s_pad,) + x.shape), t)

    carry = dict(zip(
        ("params", "opt"),
        jax.jit(lambda p, o: (bcast_rows(p), bcast_rows(o)),
                out_shardings=(row_shard, row_shard))(one, opt_one)))
    carry["round"] = jax.device_put(jnp.zeros((), jnp.int32), repl)
    if prox:
        # dense FedProx anchors round 0 at the shared init (all rows equal)
        carry["anchor"] = jax.device_put(one, repl)
    if quant:
        # compressed-path convention: reference zero, so round 0's delta
        # IS the dense bootstrap upload
        carry["ref"] = jax.device_put(
            jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), one),
            repl)
        carry["ef"] = jax.jit(
            lambda: jax.tree.map(
                lambda x: jnp.zeros((s_pad,) + x.shape, jnp.float32), one),
            out_shardings=row_shard)()

    rows, rep = PartitionSpec("site"), PartitionSpec()
    carry_specs = {"params": rows, "opt": rows, "round": rep}
    if prox:
        carry_specs["anchor"] = rep
    if quant:
        carry_specs.update(ref=rep, ef=rows)
    xs_specs = {"batches": rows, "lidx": rows, "valid": rows, "w": rows,
                "pod": rows}

    def device_step(c, x):
        lidx, valid = x["lidx"][0], x["valid"][0]
        w, pod = x["w"][0], x["pod"][0]
        batches = jax.tree.map(lambda b: b[0], x["batches"])
        st = {"params": jax.tree.map(lambda a: a[lidx], c["params"]),
              "opt": jax.tree.map(lambda a: a[lidx], c["opt"]),
              "strategy": {"global": c["anchor"]} if prox else {},
              "round": c["round"]}
        st, metrics = fl_round(st, batches,
                               {"active": jnp.ones((k_cap,), bool),
                                "partner": identity_k,
                                "is_receiver": no_recv_k})
        new_p, new_o = st["params"], st["opt"]
        if quant:
            u = jax.tree.map(
                lambda p, g, e: p.astype(jnp.float32) - g[None] + e[lidx],
                new_p, c["ref"], c["ef"])
            with jax.named_scope("quantize"):
                vals = _qdq_tree(u, chunkw, align, codec.name)
        else:
            vals = new_p

        def mask_pad(v):
            m = valid.reshape((-1,) + (1,) * (v.ndim - 1))
            return jnp.where(m, v.astype(jnp.float32), 0.0)

        # padded slots gather a clipped throwaway row — zero them so the
        # fold (weight 0) can never be polluted by a non-finite garbage
        # value (0 · nan = nan)
        with jax.named_scope("fold"):
            flat, layout = engine.flatten(jax.tree.map(mask_pad, vals))
            wk = w * valid.astype(jnp.float32)                      # [K]
            onehot = (pod[None, :] == jnp.arange(num_pods)[:, None]
                      ).astype(jnp.float32)                         # [P, K]
            wp = onehot * wk[None, :]
            # per-device partial pod sums; ONE O(P·N) psum crosses the mesh
            pod_num = jax.lax.psum(jnp.einsum("pk,kn->pn", wp, flat),
                                   "site")
            pod_tot = jax.lax.psum(jnp.sum(wp, axis=1), "site")
            pod_mean = pod_num / (pod_tot[:, None] + 1e-12)
            pod_w = ((pod_tot > 0).astype(jnp.float32) if inter == "uniform"
                     else pod_tot)
            gflat = jnp.einsum("p,pn->n", pod_w / (jnp.sum(pod_w) + 1e-12),
                               pod_mean)
            gtree = engine.unflatten(gflat, layout)
        c2 = {"round": c["round"] + 1}
        if quant:
            gbc = jax.tree.map(jnp.add, c["ref"], gtree)
            c2["ref"] = gbc
            if error_feedback:
                c2["ef"] = jax.tree.map(
                    lambda e, p_, d_: e.at[lidx].set(jnp.subtract(p_, d_),
                                                     mode="drop"),
                    c["ef"], u, vals)
            else:
                c2["ef"] = c["ef"]
        else:
            gbc = gtree
        if prox:
            c2["anchor"] = gbc
        c2["params"] = jax.tree.map(
            lambda a, g: a.at[lidx].set(
                jnp.broadcast_to(g[None], (k_cap,) + g.shape).astype(a.dtype),
                mode="drop"),
            c["params"], gbc)
        c2["opt"] = jax.tree.map(
            lambda a, v: a.at[lidx].set(v, mode="drop"), c["opt"], new_o)
        losses = jnp.full((s_loc,), jnp.nan, jnp.float32).at[lidx].set(
            metrics["loss"].astype(jnp.float32), mode="drop")
        return c2, losses

    step = jax.shard_map(device_step, mesh=mesh,
                         in_specs=(carry_specs, xs_specs),
                         out_specs=(carry_specs, rows), check_vma=False)

    w_all = np.zeros(s_pad, np.float32)
    w_all[:num_sites] = case_w / case_w.sum()
    w_all_dev = jax.device_put(jnp.asarray(w_all), row_shard)

    def _global_mean(params, w):
        flat, layout = engine.flatten(params)
        g = jax.lax.psum(jnp.einsum("s,sn->n", w, flat), "site")
        return engine.unflatten(g, layout)

    global_mean = jax.jit(jax.shard_map(_global_mean, mesh=mesh,
                                        in_specs=(rows, rows), out_specs=rep,
                                        check_vma=False))

    def site_rows(site: int, r: int):
        ks = [bundle.sample(site, r * steps + k) for k in range(steps)]
        return {key: np.stack([x[key] for x in ks]) for key in ks[0]}

    def round_xs(r: int):
        cache = {int(s): site_rows(int(s), r) for s in np.unique(gsite_a[r])}
        keys = next(iter(cache.values())).keys()
        batches = {key: np.stack([np.stack(
            [cache[int(gsite_a[r, d, i])][key] for i in range(k_cap)])
            for d in range(num_devices)]) for key in keys}
        xs = {"batches": batches, "lidx": lidx_a[r], "valid": valid_a[r],
              "w": w_a[r], "pod": pod_a[r]}
        return jax.device_put(xs, row_shard)

    runner = _ChunkRunner(step)
    recorder = job.recorder(rounds, num_sites)
    for r in range(rounds):
        with recorder.chunk(r, 1):
            with telemetry.span("repro.chunk.inputs") as inputs:
                xs = round_xs(r)
                inputs.set(bytes=_nbytes(xs))
            carry, losses_dev, exec_s = runner.run(1, carry, xs)

            def global_fn(c=carry):
                return (c["ref"] if quant
                        else global_mean(c["params"], w_all_dev))

            on_grid = (recorder.store is not None
                       and r % job.ckpt_every == 0) or r == rounds - 1
            with telemetry.span("repro.chunk.record"):
                recorder.record(r, np.asarray(losses_dev)[:num_sites],
                                participate[r],
                                global_fn=global_fn if on_grid else None,
                                extra={"step_s": exec_s,
                                       "participants": int(
                                           participate[r].sum()),
                                       "k_cap": k_cap})

    uploads = int(participate.sum())
    if quant:
        enc = _encoded_nbytes(stacked_one, chunkw, align)
        comm = {"upload_bytes": uploads * enc,
                "upload_raw_bytes": uploads * dense_nbytes,
                "download_bytes": uploads * dense_nbytes,
                "total_bytes": uploads * (enc + dense_nbytes),
                "upload_count": uploads, "download_count": uploads,
                "compression": codec.name, "down_compression": "none",
                "simulated": True}
        if topo.is_pods:
            from repro.core.topology import simulated_pods_comm
            comm.update(simulated_pods_comm(topo, participate, dense_nbytes,
                                            intra_upload_bytes=uploads * enc,
                                            compression=codec.name))
    elif topo.is_pods:
        from repro.core.topology import simulated_pods_comm
        comm = simulated_pods_comm(topo, participate, dense_nbytes)
    else:
        comm = {"upload_bytes": uploads * dense_nbytes,
                "download_bytes": uploads * dense_nbytes,
                "total_bytes": 2 * uploads * dense_nbytes,
                "upload_count": uploads, "download_count": uploads,
                "compression": "none", "down_compression": "none",
                "simulated": True}
    comm.update({"sharded": True, "devices": num_devices, "k_cap": k_cap})

    global_params = (carry["ref"] if quant
                     else global_mean(carry["params"], w_all_dev))
    state = {"params": jax.tree.map(lambda x: x[:num_sites], carry["params"]),
             "opt": jax.tree.map(lambda x: x[:num_sites], carry["opt"]),
             "strategy": {"global": carry["anchor"]} if prox else {},
             "round": carry["round"]}
    return recorder.result(global_params, transport="stacked",
                           scheduler=scheduler.name, state=state, comm=comm,
                           compile_s=runner.compile_s,
                           privacy=job.privacy_report(rounds))


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def execute_stacked(job, bundle, scheduler, codec, rounds: int,
                    resume_round: Optional[int] = None,
                    down_codec=None) -> Optional[JobResult]:
    """Run ``job`` on the compiled scan engine, or return ``None`` when
    the engine cannot replicate the job's semantics (the caller falls
    back to the retired per-round loop):

      * ``topk-sparse`` uploads (data-dependent index payloads — the
        fixed-k ``topk-fixed`` variant compiles),
      * buffered runs whose ``max_staleness`` reaches past the
        ``keep_globals`` decode-reference ring,
      * ``topk-sparse`` downloads under bidirectional compression.

    ``device_data=True`` is an explicit request for on-device batch
    generation (token tasks AND the jnp dose/seg generators) and raises
    when the combination doesn't support it.
    """
    buffered = isinstance(scheduler, BufferedScheduler)
    down = down_codec is not None and down_codec.name != "none"
    if job.device_data:
        if (buffered or codec.name != "none" or down
                or job.strategy == "pooled"
                or getattr(bundle, "traced_stacked", None) is None):
            raise ValueError(
                "device_data=True (on-device batch generation) currently "
                "supports sync uncompressed jobs whose task has a traced "
                "generator (tokens, and dose/seg without site_pools); use "
                "host batches for buffered scheduling or compressed "
                "uploads/downloads")
        if job.pod_dropout:
            raise ValueError(
                "device_data=True runs the Algorithm-2 chain on device, "
                "which covers the site tier only; pod_dropout needs the "
                "host-precomputed schedule (device_data=False)")
    if codec.name not in ("none", "int8", "fp8", "topk-fixed"):
        return None
    if down and down_codec.name not in ("int8", "fp8", "topk-fixed"):
        return None
    if buffered:
        if compress_past_ring(scheduler, codec) or codec.name == "topk-fixed":
            return None        # flat-chunk qdq only; top-k buffers host-side
        return _run_buffered_scan(job, bundle, scheduler, rounds, codec,
                                  resume_round)
    if codec.name != "none" or down:
        return _run_compressed_scan(job, bundle, scheduler, rounds, codec,
                                    resume_round,
                                    down_codec=down_codec if down else None)
    return _run_sync_scan(job, bundle, scheduler, rounds, resume_round)


def compress_past_ring(scheduler: BufferedScheduler, codec) -> bool:
    """True when compressed-buffered staleness could outlive the decode
    ring — the one buffered configuration the host loop still owns."""
    return (codec.name != "none"
            and scheduler.max_staleness >= KEEP_GLOBALS_DEFAULT)
