"""The federated round driver — builds the jitted `fl_round` step.

One FL round (Figs 3/4, Algorithm 1):

  1. (decentralized) pre-exchange: receive peer model + regional DCML
  2. local training: ``local_steps`` optimizer steps per site, vmapped
     over the stacked site axis (each site sees only its own batch shard)
  3. (centralized) post-exchange: weighted aggregation + broadcast
  4. dropout semantics: "shutdown" sites skip (2); inactive sites always
     skip exchanges (their aggregation weight is zero and they keep
     their local weights)

Host-side per-round inputs (active mask, gossip pairing) come from
``repro.core.dropout.SiteAvailability`` and ``repro.core.gossip`` —
mirroring the paper's coordination server, which tracks metadata outside
the training engine.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FederationConfig, JobConfig, MeshConfig
from repro.core import stacking
from repro.core.topology import FLAT, Topology
from repro.core.strategies import base as strat_base
# strategy modules self-register on import
from repro.core.strategies import fedavg as _f  # noqa: F401
from repro.core.strategies import fedprox as _p  # noqa: F401
from repro.core.strategies import gcml as _g  # noqa: F401
from repro.core.strategies import individual as _i  # noqa: F401
from repro.optim import Optimizer, apply_updates, clip_by_global_norm


@dataclasses.dataclass
class FLContext:
    """Everything a strategy hook may need (static, captured at trace time)."""
    fed: FederationConfig
    mesh: MeshConfig
    case_weights: jnp.ndarray
    loss_fn: Callable            # (params, batch) -> (loss, metrics)
    logits_fn: Optional[Callable]  # (params, batch) -> (logits, labels)
    optimizer: Optimizer
    grad_clip: float
    dcml_lr: float
    # where aggregation happens (flat star vs two-tier pods) — replaces
    # the old ``hierarchical`` bool; see repro.core.topology
    topology: Topology = FLAT
    microbatch: Optional[int] = None   # per-site microbatch for grad accumulation
    accum_dtype: Any = jnp.float32     # grad-accumulator dtype (bf16 for ≥236B)
    # DP-SGD (repro.privacy.dp.DPConfig or None): clip+noise inside the
    # site update, keys derived from (seed, fl_state["round"], global
    # site id, local step).  ``dp_site_base`` maps this context's site
    # rows onto GLOBAL site ids (a 1-site socket worker's row 0 is its
    # real site id), so every transport draws the same noise stream.
    privacy: Optional[Any] = None
    dp_site_base: int = 0
    # robust site→global combine (repro.core.agg_engine.AggregatorSpec
    # or its string form; None = fedavg).  Rides the context so the
    # compiled scan body dispatches the rule on-device with no change
    # to the round carry.
    aggregator: Optional[Any] = None
    # deterministic Byzantine fault injection
    # (repro.core.adversary.AdversaryPlan or None).  Stacked engines
    # apply it inside fl_round; socket workers get adversary=None here
    # and perturb their upload payload host-side instead.
    adversary: Optional[Any] = None

    def scalar_loss_fn(self, params, batch):
        return self.loss_fn(params, batch)[0]


def init_fl_state(ctx: FLContext, init_params_fn, key):
    """Round-0 federated state: identical params on every site (paper)."""
    params = stacking.init_stacked(init_params_fn, key, ctx.fed.num_sites)
    opt = jax.vmap(ctx.optimizer.init)(params)
    strategy = strat_base.get_strategy(ctx.fed.strategy)
    return {
        "params": params,
        "opt": opt,
        "strategy": strategy.init_state(params, ctx),
        "round": jnp.zeros((), jnp.int32),
    }


def make_round_inputs(ctx: FLContext, availability=None, rng=None,
                      round_index: int = 0,
                      active: Optional[np.ndarray] = None) -> Dict[str, np.ndarray]:
    """Host-side coordinator outputs for one round.

    ``active`` overrides the availability chain with a precomputed mask
    (transports that replay the Algorithm-2 schedule deterministically
    pass the round's mask directly).
    """
    from repro.core.gossip import pair_sites
    s = ctx.fed.num_sites
    if active is None:
        active = (availability.step() if availability is not None
                  else np.ones((s,), bool))
    active = np.asarray(active, bool)
    partner = np.arange(s)
    is_recv = np.zeros(s, bool)
    if strat_base.get_strategy(ctx.fed.strategy).needs_pairing:
        rng = rng or np.random.default_rng(round_index)
        partner, is_recv, _ = pair_sites(active, rng)
    return {"active": active, "partner": partner, "is_receiver": is_recv}


def make_round_inputs_traced(ctx: FLContext, key, active):
    """Traced path of :func:`make_round_inputs` — the coordinator outputs
    (gossip pairing) produced on-device from a jax PRNG key, so the
    compiled round engine (``repro.core.round_engine``) can run many
    rounds in one ``lax.scan`` without host re-entry.

    ``active`` is this round's [S] bool mask (thread it through
    :func:`repro.core.dropout.availability_step_traced` for on-device
    Algorithm-2 churn).  The pairing *law* matches the host path; the
    random streams differ (numpy PCG64 vs jax threefry), so use the host
    path when bit-parity with a replayed schedule matters.
    """
    s = ctx.fed.num_sites
    active = jnp.asarray(active, bool)
    partner = jnp.arange(s)
    is_recv = jnp.zeros(s, bool)
    if strat_base.get_strategy(ctx.fed.strategy).needs_pairing:
        from repro.core.gossip import pair_sites_traced
        partner, is_recv, _ = pair_sites_traced(key, active)
    return {"active": active, "partner": partner, "is_receiver": is_recv}


def build_fl_round(ctx: FLContext, remat_local: bool = False):
    """Returns ``fl_round(fl_state, batches, round_inputs) -> (fl_state, metrics)``.

    ``batches`` pytree leaves are shaped [S, local_steps, per-site batch…];
    for GCML, ``round_inputs`` additionally carries ``dcml_batch`` and
    ``val_batch`` with leaves [S, …].
    """
    strategy = strat_base.get_strategy(ctx.fed.strategy)
    dp = ctx.privacy
    if dp is not None and ctx.microbatch:
        raise ValueError("DP-SGD composes its own per-example/per-site "
                         "clipping; microbatch gradient accumulation is "
                         "not supported alongside it")

    def site_train_step(params, opt, batch, strat_ref, noise_key=None):
        def lf(p, b):
            # backward ops read transpose(jvp(forward)) in the trace
            with jax.named_scope("forward"):
                loss, metrics = ctx.loss_fn(p, b)
                loss = loss + strategy.local_loss_extra(p, strat_ref, ctx)
            return loss, metrics

        if dp is not None:
            from repro.privacy.dp import dp_gradients
            # DP clipping REPLACES ctx.grad_clip — the clip norm is the
            # mechanism's sensitivity, a second rescale would break the
            # accountant's calibration
            grads, loss, metrics, gnorm = dp_gradients(
                lf, params, batch, noise_key, dp)
            with jax.named_scope("optimizer"):
                updates, opt = ctx.optimizer.update(grads, opt, params)
                params = apply_updates(params, updates)
            return params, opt, {"loss": loss, "grad_norm": gnorm,
                                 **metrics}

        bsz = jax.tree.leaves(batch)[0].shape[0]
        if ctx.microbatch and ctx.microbatch < bsz:
            # gradient accumulation over microbatches (fp32 accumulators)
            n = bsz // ctx.microbatch
            micro = jax.tree.map(
                lambda x: x.reshape((n, ctx.microbatch) + x.shape[1:]), batch)

            def accum(carry, mb):
                g_acc, l_acc = carry
                (loss, metrics), g = jax.value_and_grad(
                    lf, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b_: a + b_.astype(ctx.accum_dtype), g_acc, g)
                return (g_acc, l_acc + loss), metrics

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, ctx.accum_dtype), params)
            (grads, loss_sum), ms = jax.lax.scan(accum, (g0, jnp.zeros(())), micro)
            grads = jax.tree.map(lambda g: g / n, grads)
            loss = loss_sum / n
            metrics = jax.tree.map(lambda x: x[-1], ms)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                lf, has_aux=True)(params, batch)
        with jax.named_scope("optimizer"):
            if ctx.grad_clip:
                grads, gnorm = clip_by_global_norm(grads, ctx.grad_clip)
            else:
                gnorm = jnp.zeros(())
            updates, opt = ctx.optimizer.update(grads, opt, params)
            params = apply_updates(params, updates)
        return params, opt, {"loss": loss, "grad_norm": gnorm, **metrics}

    if remat_local:
        site_train_step = jax.checkpoint(site_train_step)

    def local_phase(fl_state, batches, active):
        strat_ref = fl_state["strategy"]

        if dp is not None:
            # noise keys threaded through the carry: fl_state["round"]
            # rides every engine's scan carry, so fold_in(round, site,
            # step) replays identically across scan/loop/socket paths
            # and across crash-resume re-entry
            from repro.privacy.dp import round_key, site_step_key
            rkey = round_key(dp, fl_state["round"])
            s = jax.tree.leaves(batches)[0].shape[0]
            site_ids = jnp.arange(s, dtype=jnp.int32) + ctx.dp_site_base

            def per_site_dp(params, opt, site_batches, site_id):
                k = jax.tree.leaves(site_batches)[0].shape[0]

                def body(carry, xs):
                    b, step = xs
                    p, o = carry
                    p, o, m = site_train_step(
                        p, o, b, strat_ref,
                        site_step_key(rkey, site_id, step))
                    return (p, o), m
                (params, opt), ms = jax.lax.scan(
                    body, (params, opt),
                    (site_batches, jnp.arange(k, dtype=jnp.int32)))
                return params, opt, jax.tree.map(lambda x: x[-1], ms)

            new_params, new_opt, metrics = jax.vmap(
                per_site_dp, in_axes=(0, 0, 0, 0))(
                fl_state["params"], fl_state["opt"], batches, site_ids)
        else:
            def per_site(params, opt, site_batches):
                def body(carry, b):
                    p, o = carry
                    p, o, m = site_train_step(p, o, b, strat_ref)
                    return (p, o), m
                (params, opt), ms = jax.lax.scan(body, (params, opt),
                                                 site_batches)
                return params, opt, jax.tree.map(lambda x: x[-1], ms)

            new_params, new_opt, metrics = jax.vmap(
                per_site, in_axes=(0, 0, 0))(fl_state["params"],
                                             fl_state["opt"], batches)

        if ctx.fed.dropout_scenario == "shutdown":
            # workstation off: inactive sites neither train nor update state
            new_params = stacking.where_site(active, new_params, fl_state["params"])
            new_opt = stacking.where_site(active, new_opt, fl_state["opt"])
        return {**fl_state, "params": new_params, "opt": new_opt}, metrics

    # Byzantine fault injection: the malicious set is a static pure
    # function of (plan.seed, num_sites), baked at trace time — no RNG
    # state threads through the scan carry
    adv = ctx.adversary
    adv_mask = (jnp.asarray(adv.malicious_mask(ctx.fed.num_sites))
                if adv is not None else None)

    def fl_round(fl_state, batches, round_inputs):
        active = jnp.asarray(round_inputs["active"])
        ri = {**round_inputs, "active": active}
        if adv is not None and adv.flips_labels:
            batches = adv.perturb_batches(batches, adv_mask)
        fl_state = strategy.pre_exchange(fl_state, ri, ctx)
        with jax.named_scope("site_update"):
            fl_state, metrics = local_phase(fl_state, batches, active)
        if adv is not None and adv.flips_params:
            # perturb what malicious ACTIVE sites expose to aggregation
            # (between local training and the exchange — the same seam
            # where a socket worker perturbs its upload payload).
            # post_exchange overwrites active rows with the new global,
            # so the perturbation never persists into the site's state —
            # matching sockets, where only the wire payload is dirty.
            fl_state = {**fl_state, "params": adv.perturb_stacked(
                fl_state["params"], adv_mask & active, fl_state["round"])}
        fl_state = strategy.post_exchange(fl_state, ri, ctx)
        fl_state = {**fl_state, "round": fl_state["round"] + 1}
        if "metrics" in fl_state:
            metrics = {**metrics, **fl_state.pop("metrics")}
        return fl_state, metrics

    return fl_round


def global_model(fl_state, ctx: FLContext):
    """Case-weighted global model from the current stacked params
    (what gets served / checkpointed as 'the' model)."""
    from repro.core.agg_engine import get_engine
    w = ctx.case_weights / jnp.sum(ctx.case_weights)
    return get_engine().global_mean(fl_state["params"], w)
