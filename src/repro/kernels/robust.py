"""Pallas TPU kernels for Byzantine-robust coordinate-wise aggregation.

Robust combine rules replace the Eq. 1 weighted mean when up to ``f`` of
the stacked site rows may be adversarial (sign-flipped, rescaled, or
noised uploads).  The coordinate-wise rules are rank statistics over the
site axis, so the kernel's shape is the same streaming pass as
``fedagg``: one [S, block_n] slab per grid cell, a per-coordinate rank
over S, and one [1, block_n] write.  The TPU kernel language has no
sort, so ranks come from pairwise comparison: row i's rank is the count
of rows j with x_j < x_i, plus the rows j < i with x_j == x_i (ties
break by row index, like a stable sort).  The kept ranks are then
folded in rank order, as a sort would leave them, so the result is
bit-identical under any permutation of the rows.  That is O(S²) VPU work
per coordinate — cheap next to the HBM stream, because S is the small
site axis.

Masked-row awareness: rows with ``active == 0`` (Algorithm-2 dropout,
client sampling) are pushed to +inf before ranking, so they fall past
every active rank; the trim depth and the divisor use the *traced*
active count, which is what lets the rule compile into the multi-round
``lax.scan`` where the active mask changes per round.

  trimmed mean  f  — drop the f smallest and f largest active values per
                     coordinate (f clamps to ⌊(k−1)/2⌋ for k active
                     rows, so the keep set is never empty), mean the
                     rest.  UNWEIGHTED over the keep set: rank rules and
                     case weights don't compose (a 100×-weighted
                     adversary would defeat the trim).
  median           — the trimmed mean at maximal trim depth: for k odd
                     the middle rank, for k even the mean of the two
                     middle ranks — exactly ``trimmed_mean(f=S)``.

``_trim_block`` is the single op sequence both the kernel body and the
jnp twin (``trimmed_mean_ref``) execute, so kernel-vs-twin parity is
bit-exact by construction (tested in ``tests/test_kernels.py``).
``interpret`` defaults to compiled on TPU/GPU and to the Pallas
interpreter elsewhere, like every kernel in this package.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fedagg import _round_up, site_block_n

# [S, block_n]-sized temporaries the rank body keeps live (masked
# values, ranks, one comparison, one rank select), for the VMEM fit
_TRIM_TEMPS = 4


def _trim_block(x, a, f: int):
    """Coordinate-wise trimmed mean of the active rows of one block.

    x: [S, n] values; a: [S, 1] active mask (float, >0.5 = active); f:
    trim depth.  Returns [1, n].  Inactive rows rank past every active
    row (+inf, ties by index); the where-before-sum keeps ``inf · 0``
    out of the fold.
    """
    x = x.astype(jnp.float32)
    act = a > 0.5                                         # [S, 1]
    k = jnp.sum(act.astype(jnp.int32))
    xm = jnp.where(act, x, jnp.inf)
    row = jax.lax.broadcasted_iota(jnp.int32, xm.shape, 0)
    rank = jnp.zeros(xm.shape, jnp.int32)
    for j in range(xm.shape[0]):                          # static, S small
        xj = xm[j:j + 1, :]
        rank += ((xj < xm) | ((xj == xm) & (row > j))).astype(jnp.int32)
    fe = jnp.minimum(jnp.int32(f), jnp.maximum(k - 1, 0) // 2)
    # fold the kept ranks in rank order, so the sum (and so the result)
    # is bit-identical under any permutation of the site rows
    total = jnp.zeros((1, xm.shape[1]), jnp.float32)
    for r in range(xm.shape[0]):
        v = jnp.sum(jnp.where(rank == r, xm, 0.0), axis=0, keepdims=True)
        total = total + jnp.where((r >= fe) & (r < k - fe), v, 0.0)
    return total / jnp.maximum(k - 2 * fe, 1).astype(jnp.float32)


def _trimmed_kernel(f, x_ref, a_ref, o_ref):
    o_ref[...] = _trim_block(x_ref[...], a_ref[...], f).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("f", "block_n", "interpret"))
def trimmed_mean(stacked, active, f: int, *, block_n: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Coordinate-wise trimmed mean over the active rows of [S, N].

    stacked: [S, N] flattened params; active: [S] mask; f: rows trimmed
    from each end of the per-coordinate order.  Returns [N] fp32.
    """
    if interpret is None:
        interpret = jax.default_backend() not in ("tpu", "gpu")
    s, n = stacked.shape
    active = jnp.asarray(active, jnp.float32).reshape(s, 1)
    block_n = site_block_n(s, n, block_n, temps=_TRIM_TEMPS)
    padded = _round_up(n, block_n)
    if padded != n:
        stacked = jnp.pad(stacked, ((0, 0), (0, padded - n)))
    out = pl.pallas_call(
        functools.partial(_trimmed_kernel, f),
        grid=(padded // block_n,),
        in_specs=[
            pl.BlockSpec((s, block_n), lambda i: (0, i)),
            pl.BlockSpec((s, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded), jnp.float32),
        interpret=interpret,
    )(stacked, active)
    return out[0, :n]


def masked_median(stacked, active, *, block_n: Optional[int] = None,
                  interpret: Optional[bool] = None):
    """Coordinate-wise median over the active rows of [S, N] — the
    trimmed mean at maximal trim depth (f = S clamps to ⌊(k−1)/2⌋)."""
    return trimmed_mean(stacked, active, int(stacked.shape[0]),
                        block_n=block_n, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("f",))
def trimmed_mean_ref(stacked, active, f: int):
    """jnp twin of :func:`trimmed_mean` — the identical op sequence on
    the whole [S, N] buffer (the CPU engine path; bit-exact vs the
    kernel because both run :func:`_trim_block` elementwise over N)."""
    stacked = jnp.asarray(stacked)
    act = jnp.asarray(active, jnp.float32).reshape(stacked.shape[0], 1)
    return _trim_block(stacked, act, f)[0]


def masked_median_ref(stacked, active):
    """jnp twin of :func:`masked_median`."""
    return trimmed_mean_ref(stacked, active, int(jnp.shape(stacked)[0]))
