"""The site update's form: the S sites' local steps run one after another
(``lax.map``) where a site's step holds a convolution, and vmapped where
it does not, with the same numbers either way."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.api import FederatedJob, TaskConfig
from repro.core import federation
from repro.optim import sgd

TINY_SANET = dict(kind="dose", sites=4, batch=1, volume=(8, 8, 8),
                  base_filters=8, num_levels=2, seed=0)


def _site_update_spans():
    return [(s.attrs["form"], s.attrs["sites"]) for s in telemetry.spans()
            if s.name == "repro.site_update"]


def _plain_sgd(monkeypatch, lr=0.05):
    """The job's context with SGD in place of AdamW: AdamW turns the
    rounding of a zero true gradient (a conv bias ahead of a GroupNorm)
    into a whole ``lr`` step, which would hide the comparison."""
    orig = FederatedJob.context

    def context(self, *a, **kw):
        return dataclasses.replace(orig(self, *a, **kw), optimizer=sgd(lr))
    monkeypatch.setattr(FederatedJob, "context", context)


def _assert_trees_close(a, b, rtol=1e-5, atol=1e-6):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("kw", [
    dict(),
    dict(sample="uniform:3", dropout_scenario="shutdown"),
], ids=["fedavg", "shutdown"])
def test_map_matches_vmap(kw, monkeypatch):
    """Two sync-scan rounds of a tiny SA-Net: the sequential site update
    and the vmapped one give the same per-site losses and stacked state;
    under ``shutdown`` the site left out of a round keeps its row."""
    _plain_sgd(monkeypatch)
    job = FederatedJob(task=TaskConfig(**TINY_SANET), strategy="fedavg",
                       rounds=2, round_engine="scan", seed=0, **kw)
    mapped = job.run()
    assert _site_update_spans() == [("map", 4)]
    if kw:
        masks = job.masks(2)
        assert masks[1].sum() == 3
        (off,) = np.flatnonzero(~masks[1])
        first = job.replace(rounds=1).run()
        np.testing.assert_array_equal(job.replace(rounds=1).masks(1),
                                      masks[:1])
        for got, before in zip(jax.tree.leaves(mapped.state["params"]),
                               jax.tree.leaves(first.state["params"])):
            np.testing.assert_array_equal(np.asarray(got)[off],
                                          np.asarray(before)[off])
    monkeypatch.setattr(federation, "_has_conv", lambda jaxpr: False)
    vmapped = job.run()
    assert _site_update_spans() == [("vmap", 4)]
    for hm, hv in zip(mapped.history, vmapped.history):
        assert hm["active"] == hv["active"]
        np.testing.assert_allclose(hm["per_site_loss"], hv["per_site_loss"],
                                   rtol=1e-5)
    _assert_trees_close(mapped.state["params"], vmapped.state["params"])
    _assert_trees_close(mapped.state["opt"], vmapped.state["opt"])


@pytest.mark.parametrize("task,kw,want", [
    (TINY_SANET, {}, ("map", 4)),
    (TINY_SANET, dict(dp_clip=1.0, dp_noise_multiplier=0.5), ("map", 4)),
    (dict(kind="tokens", arch="smollm-135m", sites=3, batch=2, seq=16,
          seed=0), {}, ("vmap", 3)),
], ids=["sanet", "sanet-dp", "transformer"])
def test_form_follows_the_program(task, kw, want):
    """SA-Net's site step holds convolutions and runs site by site, the
    DP branch too; a transformer's site step keeps the vmap.  The choice
    is recorded once per compiled chunk, inside its lowering."""
    FederatedJob(task=TaskConfig(**task), rounds=1, seed=0, **kw).run()
    assert _site_update_spans() == [want]
    (site_update,) = [s for s in telemetry.spans()
                      if s.name == "repro.site_update"]
    (lower,) = [s for s in telemetry.spans()
                if s.name == "repro.chunk.lower"]
    assert site_update.parent == lower.id


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1, 1), "SAME",
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))


@pytest.mark.parametrize("fn,want", [
    (lambda w, x: jnp.sum(_conv(x, w)), True),
    (lambda w, x: jnp.sum(jax.grad(lambda w_: jnp.sum(_conv(x, w_)))(w)),
     True),
    (lambda w, x: jax.lax.scan(
        lambda c, _: (c + jnp.sum(jax.checkpoint(_conv)(x, w)), None),
        0.0, None, length=2)[0], True),
    (lambda w, x: jnp.sum(x.reshape(-1, 2) @ w.reshape(2, -1)), False),
], ids=["conv", "conv-grad", "conv-in-scan-remat", "matmul"])
def test_conv_found_in_nested_programs(fn, want):
    """A convolution is found wherever the site step nests it."""
    w = jnp.zeros((3, 3, 3, 2, 2))
    x = jnp.zeros((1, 4, 4, 4, 2))
    assert federation._has_conv(jax.make_jaxpr(fn)(w, x).jaxpr) is want
