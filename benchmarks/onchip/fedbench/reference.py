"""Plain reference of what a cell trains: SA-Net, its task loss, global-
norm clipping, AdamW and the Eq. 1 fold, written from the paper's
description in straightforward ``jax.numpy`` with no kernels, batching
over sites or engine.  It imports nothing of the program.

* SA-Net (FedKBP+, arXiv:2505.14507, Fig. 5): a stem convolution; per
  encoder level two pre-activation residual blocks with squeeze-and-
  excitation (GroupNorm → ReLU → 3³ conv, twice, then SE, plus a 1³
  projection where the width changes) and a stride-2 3³ convolution
  between levels; per decoder level a nearest-neighbour upsample and 1³
  convolution, plus a scale-attention block that resizes every encoder
  scale to the level's resolution, projects it by a 1³ convolution,
  sums, squeezes (global average pool, SE over all scales) and weights
  the scales by a softmax across scales; one residual block; a 1³ deep-
  supervision head resized to the input.  The last head is the output.
* Losses: dose — masked voxel MAE; segmentation — mean cross-entropy plus
  mean soft Jaccard distance.  Deep supervision adds half the mean of the
  other heads' losses.
* Optimiser: gradients clipped to a global L2 norm, then AdamW with bias
  correction and decoupled weight decay.
* Round: every site takes its local steps from the shared global, then the
  global becomes the case-count-weighted mean of the sites (Eq. 1); every
  site keeps its own AdamW moments.

Initialisation follows the same key schedule as the published code path
(``split(key, 64)`` consumed in module order, He-scaled truncated normal
convolutions, zero biases), so the same seed gives the same weights
without reading any the program made.

``precision`` picks how convolutions and matrix products are taken:
``"highest"`` is full float32; ``"bf16"`` takes them in bfloat16, the
next precision below float32, which is the control.
"""
from __future__ import annotations

import functools
import json
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

DIMNUMS = ("NDHWC", "DHWIO", "NDHWC")
GROUPS = 8
GN_EPS = 1e-5
DS_WEIGHT = 0.5
CLIP_EPS = 1e-9
FOLD_EPS = 1e-12


def filters(cfg: dict, level: int) -> int:
    return cfg["base_filters"] * 2 ** level


# ---------------------------------------------------------------- init


def _conv_init(key, k, cin, cout):
    fan_in = cin * k[0] * k[1] * k[2]
    w = jax.random.truncated_normal(key, -2, 2, k + (cin, cout)) * (
        2.0 / fan_in) ** 0.5
    return {"w": w.astype(jnp.float32), "b": jnp.zeros((cout,), jnp.float32)}


def _norm_init(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _se_init(key, c, ratio):
    k1, k2 = jax.random.split(key)
    hidden = max(c // ratio, 4)
    return {"w1": jax.random.normal(k1, (c, hidden)) * c ** -0.5,
            "w2": jax.random.normal(k2, (hidden, c)) * hidden ** -0.5}


def _block_init(key, cin, cout, ratio):
    ks = jax.random.split(key, 4)
    p = {"norm1": _norm_init(cin),
         "conv1": _conv_init(ks[0], (3, 3, 3), cin, cout),
         "norm2": _norm_init(cout),
         "conv2": _conv_init(ks[1], (3, 3, 3), cout, cout),
         "se": _se_init(ks[2], cout, ratio)}
    if cin != cout:
        p["proj"] = _conv_init(ks[3], (1, 1, 1), cin, cout)
    return p


def init_params(key, cfg: dict):
    levels, ratio = cfg["num_levels"], cfg["se_ratio"]
    ks = iter(jax.random.split(key, 64))
    p = {"stem": _conv_init(next(ks), (3, 3, 3), cfg["in_channels"],
                            filters(cfg, 0))}
    p["enc"] = []
    for lvl in range(levels):
        c = filters(cfg, lvl)
        blocks = {"b1": _block_init(next(ks), c, c, ratio),
                  "b2": _block_init(next(ks), c, c, ratio)}
        if lvl < levels - 1:
            blocks["down"] = _conv_init(next(ks), (3, 3, 3), c,
                                        filters(cfg, lvl + 1))
        p["enc"].append(blocks)
    p["scale_attn"] = []
    for lvl in range(levels - 1):
        c = filters(cfg, lvl)
        sk = jax.random.split(next(ks), levels + 1)
        p["scale_attn"].append({
            "proj": [_conv_init(sk[i], (1, 1, 1), filters(cfg, i), c)
                     for i in range(levels)],
            "se": _se_init(sk[-1], c * levels, ratio)})
    p["dec"], p["ds_heads"] = [], []
    for lvl in range(levels - 2, -1, -1):
        cin, cout = filters(cfg, lvl + 1), filters(cfg, lvl)
        up = _conv_init(next(ks), (1, 1, 1), cin, cout)
        block = _block_init(next(ks), cout, cout, ratio)
        p["dec"].append({"up": up, "block": block})
        p["ds_heads"].append(_conv_init(next(ks), (1, 1, 1), cout,
                                        cfg["out_channels"]))
    return p


# ---------------------------------------------------------------- forward


class Arith:
    """How products are taken: in full float32, or in bfloat16 (operands
    and results rounded to bfloat16, the backward pass likewise)."""

    def __init__(self, precision: str):
        if precision not in ("highest", "bf16"):
            raise ValueError(f"unknown reference precision {precision!r}")
        self.dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
        self.precision = (None if precision == "bf16"
                          else jax.lax.Precision.HIGHEST)

    def conv(self, p, x, stride=1):
        y = jax.lax.conv_general_dilated(
            x.astype(self.dtype), p["w"].astype(self.dtype), (stride,) * 3,
            "SAME", dimension_numbers=DIMNUMS, precision=self.precision)
        return y.astype(jnp.float32) + p["b"]

    def matmul(self, a, b):
        return jnp.matmul(a.astype(self.dtype), b.astype(self.dtype),
                          precision=self.precision).astype(jnp.float32)


def _groupnorm(p, x):
    b, c = x.shape[0], x.shape[-1]
    g = min(GROUPS, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, -1, g, c // g)
    mean = jnp.mean(xg, axis=(1, 3), keepdims=True)
    var = jnp.var(xg, axis=(1, 3), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + GN_EPS)
    return xg.reshape(x.shape) * p["scale"] + p["bias"]


def _se(ar, p, x):
    s = jnp.mean(x, axis=(1, 2, 3))
    s = ar.matmul(jax.nn.relu(ar.matmul(s, p["w1"])), p["w2"])
    return x * jax.nn.sigmoid(s)[:, None, None, None, :]


def _block(ar, p, x):
    h = ar.conv(p["conv1"], jax.nn.relu(_groupnorm(p["norm1"], x)))
    h = ar.conv(p["conv2"], jax.nn.relu(_groupnorm(p["norm2"], h)))
    h = _se(ar, p["se"], h)
    skip = ar.conv(p["proj"], x) if "proj" in p else x
    return skip + h


def _resize(x, spatial):
    return jax.image.resize(x, (x.shape[0],) + tuple(spatial) + (x.shape[-1],),
                            method="nearest")


def _scale_attn(ar, p, feats, cfg, level):
    levels = cfg["num_levels"]
    c = filters(cfg, level)
    target = feats[level].shape[1:4]
    maps = [ar.conv(p["proj"][i], _resize(f, target))
            for i, f in enumerate(feats)]
    total = maps[0]
    for m in maps[1:]:
        total = total + m
    s = jnp.tile(jnp.mean(total, axis=(1, 2, 3)), (1, levels))
    e = ar.matmul(jax.nn.relu(ar.matmul(s, p["se"]["w1"])), p["se"]["w2"])
    weights = jax.nn.softmax(e.reshape(s.shape[0], levels, c), axis=1)
    out = weights[:, 0][:, None, None, None, :] * maps[0]
    for i in range(1, levels):
        out = out + weights[:, i][:, None, None, None, :] * maps[i]
    return out


def forward(params, x, cfg: dict, ar: Arith):
    """``x [B, D, H, W, Cin]`` → list of deep-supervision outputs at full
    resolution, coarsest first; the last is the network's output."""
    levels = cfg["num_levels"]
    h = ar.conv(params["stem"], x)
    feats = []
    for lvl in range(levels):
        blk = params["enc"][lvl]
        h = _block(ar, blk["b2"], _block(ar, blk["b1"], h))
        feats.append(h)
        if lvl < levels - 1:
            h = ar.conv(blk["down"], h, stride=2)
    outs, d = [], feats[-1]
    for i, lvl in enumerate(range(levels - 2, -1, -1)):
        dec = params["dec"][i]
        up = ar.conv(dec["up"], _resize(d, feats[lvl].shape[1:4]))
        d = _block(ar, dec["block"],
                   up + _scale_attn(ar, params["scale_attn"][lvl], feats, cfg,
                                    lvl))
        outs.append(_resize(ar.conv(params["ds_heads"][i], d), x.shape[1:4]))
    return outs


def _with_deep_supervision(term, outs):
    loss = term(outs[-1])
    if len(outs) > 1:
        aux = term(outs[0])
        for o in outs[1:-1]:
            aux = aux + term(o)
        loss = loss + DS_WEIGHT * aux / (len(outs) - 1)
    return loss


def loss_fn(params, batch, cfg: dict, ar: Arith):
    outs = forward(params, batch["volume"], cfg, ar)
    if cfg["task"] == "dose":
        mask = batch["mask"]

        def term(pred):
            err = jnp.abs(pred - batch["dose"]) * mask
            return jnp.sum(err) / (jnp.sum(mask) + 1e-6)
    else:
        labels = batch["labels"]
        onehot = jax.nn.one_hot(labels, cfg["out_channels"])

        def term(logits):
            logp = jax.nn.log_softmax(logits, axis=-1)
            ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
            probs = jnp.exp(logp)
            inter = jnp.sum(probs * onehot, axis=(1, 2, 3))
            union = jnp.sum(probs + onehot, axis=(1, 2, 3)) - inter
            jac = 1.0 - (inter + 1e-6) / (union + 1e-6)
            return jnp.mean(ce) + jnp.mean(jac)
    return _with_deep_supervision(term, outs)


# ---------------------------------------------------------------- training


def site_step(params, mu, nu, step, batch, cfg: dict, opt: dict, ar: Arith):
    """One local step of one site: loss and gradient at ``params``, the
    gradient clipped to ``opt['grad_clip']`` in global norm, one AdamW
    update.  Returns (params, mu, nu, loss, clipped gradient)."""
    loss, g = jax.value_and_grad(loss_fn)(params, batch, cfg, ar)
    leaves = jax.tree.leaves(g)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    g = jax.tree.map(
        lambda x: x * jnp.minimum(1.0, opt["grad_clip"] / (norm + CLIP_EPS)), g)
    b1, b2 = opt["b1"], opt["b2"]
    t = (step + 1).astype(jnp.float32)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    params = jax.tree.map(
        lambda p, m, v: p - opt["lr"] * ((m / c1) / (jnp.sqrt(v / c2)
                                                     + opt["eps"])
                                         + opt["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu, loss, g


def path_norms(tree) -> Dict[str, float]:
    """``{leaf path: L2 norm}`` in float64."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): float(np.linalg.norm(
        np.asarray(x, np.float64))) for path, x in flat}


@functools.lru_cache(maxsize=8)
def _programs(cfg_json: str, opt_json: str, precision: str):
    """The jitted site step and initialiser, one pair per configuration
    and precision, so that repeated runs in one process trace once."""
    cfg, opt, ar = json.loads(cfg_json), json.loads(opt_json), Arith(precision)
    step = jax.jit(lambda p, m, v, t, b: site_step(p, m, v, t, b, cfg, opt,
                                                   ar))
    return step, jax.jit(lambda k: init_params(k, cfg))


def run_rounds(cfg: dict, opt: dict, site_cases: List[int],
               batch_fn: Callable[[int, int], Dict[str, np.ndarray]],
               init_key, rounds: int, local_steps: int,
               precision: str, devices=None) -> Dict[str, object]:
    """Drive ``rounds`` federated rounds of the reference, one site at a
    time (site ``s`` on ``devices[s % len(devices)]``, the fold on the
    first).  ``batch_fn(site, step)`` gives the site's ``[B, …]`` batch.

    Returns the per-round per-site losses ``[rounds, S]``, the initial
    weights (host), and per leaf path: the norm of the global's change
    from them, and per site the norm of the first clipped gradient."""
    t0, first_call = time.perf_counter(), None
    devices = list(devices or jax.devices()[:1])
    step, init_fn = _programs(json.dumps(cfg, sort_keys=True),
                              json.dumps(opt, sort_keys=True), precision)
    init = jax.device_put(init_fn(init_key), devices[0])
    sites = len(site_cases)
    on = [devices[s % len(devices)] for s in range(sites)]
    w = np.asarray(site_cases, np.float64)
    w = jnp.asarray(w / w.sum(), jnp.float32)
    mus = [jax.device_put(jax.tree.map(jnp.zeros_like, init), d) for d in on]
    nus = list(mus)
    losses = [[None] * sites for _ in range(rounds)]
    grad0 = [None] * sites
    glob = init
    for r in range(rounds):
        new = []
        for s in range(sites):
            p = jax.device_put(glob, on[s])
            for k in range(local_steps):
                t = jnp.asarray(r * local_steps + k, jnp.int32)
                b = jax.device_put(batch_fn(s, r * local_steps + k), on[s])
                p, mus[s], nus[s], loss, g = step(p, mus[s], nus[s], t, b)
                if first_call is None:
                    first_call = time.perf_counter() - t0
                if r == 0 and k == 0:
                    grad0[s] = g
            losses[r][s] = loss
            new.append(jax.device_put(p, devices[0]))
        if r == 0:
            grad0 = [path_norms(g) for g in grad0]
        wsum = jnp.sum(w) + FOLD_EPS
        glob = jax.tree.map(
            lambda *xs: sum(w[i] / wsum * xs[i] for i in range(sites)), *new)
    change = jax.tree.map(lambda a, b: a - b, glob, init)
    losses = np.asarray([[float(x) for x in row] for row in losses])
    return {"losses": losses,
            "seconds": (first_call, time.perf_counter() - t0),
            "init": jax.device_get(init),
            "change": path_norms(change),
            "grad0": {k: np.asarray([g[k] for g in grad0])
                      for k in grad0[0]}}
