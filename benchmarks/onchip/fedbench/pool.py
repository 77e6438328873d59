"""The benchmark's case pool: every site's cases, made once from the seed.

A federation reads preprocessed cases that are already in memory; it does
not synthesise a volume per round.  So set-up makes each site's whole
partition here, on the device in a few jitted calls, and keeps it on the
host as a loader would.  Each round, each site draws its batch from its
own pool, cycling through a seeded permutation of its cases, so the
first ``n_s`` draws of site ``s`` are all different cases.

The arithmetic is a copy of the program's jnp twins of its synthetic
generators (``DoseTaskGenerator`` / ``SegTaskGenerator`` in
``repro.data.synthetic``): the same geometry family, dose law and blob
classes.  It is kept here so that no later change to the program can
change what the benchmark feeds it.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 8          # cases per generator call: one compiled shape per task


def _grid(vol):
    d, h, w = vol
    return jnp.stack(jnp.meshgrid(jnp.arange(d), jnp.arange(h), jnp.arange(w),
                                  indexing="ij")).astype(jnp.float32)


def _dose_case(key, vol, num_oars: int):
    d, h, w = vol
    grid = _grid(vol)
    dims = jnp.asarray([d, h, w], jnp.float32)

    def sphere(center, radius):
        d2 = jnp.sum((grid - center[:, None, None, None]) ** 2, axis=0)
        return (d2 <= radius * radius).astype(jnp.float32)

    body = sphere(dims / 2, 0.45 * d)
    k_ct, k_c, k_r = jax.random.split(key, 3)
    ct = 0.3 * jax.random.normal(k_ct, (d, h, w)) * body
    center = dims * (0.5 + jax.random.uniform(k_c, (3,), minval=-0.14,
                                              maxval=0.14))
    r_ptv = d * jax.random.uniform(k_r, minval=0.06, maxval=0.18)
    ptv = sphere(center, r_ptv)
    oars = [sphere(center + jnp.asarray([0.0, (j + 1) * 2.2, 0.0])
                   * r_ptv * (1.0 if j % 2 == 0 else -1.0), r_ptv * 0.8)
            for j in range(num_oars)]
    dist = jnp.sqrt(jnp.sum((grid - center[:, None, None, None]) ** 2, axis=0))
    field = 70.0 * jnp.exp(-jnp.maximum(dist - r_ptv, 0.0) / (0.15 * d))
    for o in oars:
        field = field * (1.0 - 0.35 * o)
    field = field * body
    return {"volume": jnp.stack([ct, ptv] + oars, axis=-1),
            "dose": (field / 70.0)[..., None],
            "mask": body[..., None]}


def _seg_case(key, vol, in_channels: int, num_classes: int):
    d, h, w = vol
    grid = _grid(vol)
    dims = jnp.asarray([d, h, w], jnp.float32)
    gain = jnp.asarray([0.5 + 0.25 * c for c in range(in_channels)],
                       jnp.float32)
    k_noise, *k_cls = jax.random.split(key, num_classes + 1)
    lab = jnp.zeros((d, h, w), jnp.int32)
    for c in range(1, num_classes):
        k_c, k_r = jax.random.split(k_cls[c - 1])
        center = dims * (0.5 + jax.random.uniform(k_c, (3,), minval=-0.15,
                                                  maxval=0.15))
        r = d * jax.random.uniform(k_r, minval=0.10, maxval=0.20) / c
        d2 = jnp.sum((grid - center[:, None, None, None]) ** 2, axis=0)
        lab = jnp.where(d2 <= r * r, c, lab)
    base = 0.15 * jax.random.normal(k_noise, (d, h, w, in_channels))
    base = base + lab[..., None].astype(jnp.float32) * gain
    return {"volume": base.astype(jnp.float32), "labels": lab}


def case_fn(cfg: dict):
    """``keys [BLOCK] -> {name: [BLOCK, …]}`` for the configuration's task."""
    vol = tuple(cfg["volume"])
    if cfg["task"] == "dose":
        one = lambda k: _dose_case(k, vol, cfg["in_channels"] - 2)
    elif cfg["task"] == "segmentation":
        one = lambda k: _seg_case(k, vol, cfg["in_channels"],
                                  cfg["out_channels"])
    else:
        raise ValueError(f"unknown task {cfg['task']!r}")
    return jax.jit(jax.vmap(one))


class CasePool:
    """Per-site case arrays on the host plus the draw order."""

    def __init__(self, cfg: dict, site_cases: Sequence[int], key_data,
                 perm_seed: int):
        make = case_fn(cfg)
        root = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32))
        self.sites: List[Dict[str, np.ndarray]] = []
        for s, n in enumerate(site_cases):
            if n < 1:
                raise ValueError(f"site {s} has no cases")
            site_key = jax.random.fold_in(root, s)
            blocks = []
            for b0 in range(0, n, BLOCK):
                ids = jnp.arange(b0, b0 + BLOCK)
                keys = jax.vmap(lambda i: jax.random.fold_in(site_key, i))(ids)
                blocks.append(jax.device_get(make(keys)))
            self.sites.append({k: np.concatenate([b[k] for b in blocks])[:n]
                               for k in blocks[0]})
        rng = np.random.default_rng(perm_seed)
        self.order = [rng.permutation(n) for n in site_cases]

    def case_ids(self, site: int, step: int, batch: int) -> np.ndarray:
        """Pool indices of ``site``'s batch at local step ``step``."""
        order = self.order[site]
        return order[(step * batch + np.arange(batch)) % len(order)]

    def sample(self, site: int, step: int, batch: int) -> Dict[str, np.ndarray]:
        """``[B, …]`` — one site's batch at one local step."""
        ids = self.case_ids(site, step, batch)
        return {k: v[ids] for k, v in self.sites[site].items()}

    def stacked(self, rnd: int, local_steps: int, batch: int
                ) -> Dict[str, np.ndarray]:
        """``[S, K, B, …]`` — every site's batches for one round; step
        ``rnd·K + k`` as :meth:`sample` indexes it."""
        rows = [[self.sample(s, rnd * local_steps + k, batch)
                 for k in range(local_steps)] for s in range(len(self.sites))]
        return {key: np.stack([np.stack([r[key] for r in site])
                               for site in rows]) for key in rows[0][0]}

    @property
    def nbytes(self) -> int:
        return sum(v.nbytes for site in self.sites for v in site.values())
