"""Pallas TPU kernel for federated weight aggregation (paper Eq. 1).

The aggregation server's hot loop: ``global = Σ_s (m_s/m) · w_s`` over
the stacked site axis.  Purely memory-bound (one pass over S×N param
bytes), so the kernel's job is to stream HBM at full bandwidth with a
single fused multiply-accumulate per element — no intermediate global
buffers per site (which a naive ``sum`` of scaled pytrees would
allocate).

  grid = (ceil(N / block_n)); each cell loads the [S, block_n] slab into
  VMEM, multiplies by the [S, 1] weight column and sums over the site
  axis on the VPU, and writes one [1, block_n] row.

Every block is 2-D and tile-legal for Mosaic (last two dims divisible by
8×128 or equal to the array's): weights ride as an [S, 1] column and
per-chunk scales as an [S, C, 1] column, never as 1-D vectors.  Block
sizes along N (or C) default to what keeps the double-buffered [S, …]
slabs inside the default scoped VMEM for the given S (:func:`fit_block`).

Arbitrary ``N`` is supported: the buffer is zero-padded up to a block
multiple (zero columns contribute nothing and are sliced off the
output).  ``interpret`` defaults to compiled on TPU/GPU and to the
Pallas interpreter elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANE = 128   # TPU lane width — pad so compiled blocks tile cleanly
_SUBLANE = 8     # fp32 sublane tile
_INT8_ROWS = 32  # int8 sublane tile: preferred row step of int8 slabs
# Per-kernel VMEM budget for one grid cell's blocks, double buffers and
# temporaries — half of v5e's 16 MiB default scoped VMEM, leaving the
# compiler room for its own scratch.
VMEM_BUDGET = 8 * 1024 * 1024


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def fit_block(unit_bytes: int, unit: int, cap: int) -> int:
    """Largest block of ``unit`` × a power of two (at most ``cap``, at
    least ``unit``) whose footprint at ``unit_bytes`` per block element
    keeps one grid cell within :data:`VMEM_BUDGET`.  Powers of two keep
    the zero padding of power-of-two-friendly sizes at nothing."""
    fit = min(cap, VMEM_BUDGET // max(unit_bytes, 1))
    block = unit
    while block * 2 <= fit:
        block *= 2
    return block


def _fedagg_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)            # [S, block_n]
    w = w_ref[...].astype(jnp.float32)            # [S, 1]
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True).astype(o_ref.dtype)


def _fedagg_dequant_kernel(q_ref, s_ref, u_ref, w_ref, g_ref, r_ref):
    q = q_ref[...].astype(jnp.float32)            # [S, block_c, chunk]
    deq = q * s_ref[...]                          # scales [S, block_c, 1]
    r_ref[...] = u_ref[...] - deq                 # error-feedback residual
    w = w_ref[...].astype(jnp.float32)            # [S, 1, 1]
    g_ref[...] = jnp.sum(deq * w, axis=0)


def _chunk_block(block_c: Optional[int], s: int, c: int, chunk: int,
                 slabs: int) -> int:
    """Row block over C for the [S, C, chunk] kernels: ``slabs`` fp32
    [S, rows, chunk] buffers (inputs, outputs, temporaries) double-
    buffered, stepping by the int8 sublane tile where that fits and by
    the fp32 one where it does not (large S); the whole C when it fits
    in one block."""
    if block_c is None:
        row_bytes = 2 * slabs * s * chunk * 4
        block_c = fit_block(row_bytes, _INT8_ROWS, 256)
        if block_c * row_bytes > VMEM_BUDGET:
            block_c = fit_block(row_bytes, _SUBLANE, 256)
    return min(block_c, c)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def fedagg_dequant(q, scales, u, weights, *, block_c: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """Fused dequantize + weighted fold for quantized site uploads.

    The compressed round engine's server step: each site's int8 delta
    (``q`` [S, C, chunk] with per-chunk fp32 ``scales`` [S, C]) is
    dequantized and folded into the Eq. 1 weighted sum in ONE pass —
    the dense fp32 per-site models never exist in HBM.  Because error
    feedback needs exactly ``u − deQ(Q(u))``, the kernel also emits the
    next residual from the same VMEM-resident dequantized block:

      returns ``(global [C, chunk] = Σ_s weights_s · deq_s,``
      ``residual [S, C, chunk] = u − deq)``.

    ``u`` is the pre-quantization input (delta + carried residual).  One
    [S, block_c, chunk] slab per grid cell; int8 loads keep the HBM
    traffic at ~1/4 of an fp32 fold.
    """
    if interpret is None:
        interpret = jax.default_backend() not in ("tpu", "gpu")
    s, c, chunk = q.shape
    if c == 0:
        return (jnp.zeros((0, chunk), jnp.float32),
                jnp.zeros((s, 0, chunk), jnp.float32))
    block_c = _chunk_block(block_c, s, c, chunk, slabs=4)
    padded = _round_up(c, block_c)
    scales = scales.reshape(s, c, 1)
    if padded != c:
        q = jnp.pad(q, ((0, 0), (0, padded - c), (0, 0)))
        scales = jnp.pad(scales, ((0, 0), (0, padded - c), (0, 0)))
        u = jnp.pad(u, ((0, 0), (0, padded - c), (0, 0)))
    g, r = pl.pallas_call(
        _fedagg_dequant_kernel,
        grid=(padded // block_c,),
        in_specs=[
            pl.BlockSpec((s, block_c, chunk), lambda i: (0, i, 0)),
            pl.BlockSpec((s, block_c, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((s, block_c, chunk), lambda i: (0, i, 0)),
            pl.BlockSpec((s, 1, 1), lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_c, chunk), lambda i: (i, 0)),
            pl.BlockSpec((s, block_c, chunk), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, chunk), jnp.float32),
            jax.ShapeDtypeStruct((s, padded, chunk), jnp.float32),
        ],
        interpret=interpret,
    )(q, scales, u, weights.reshape(s, 1, 1))
    return (g[:c], r[:, :c]) if padded != c else (g, r)


def _dequant_install_kernel(q_ref, s_ref, b_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)            # [S, block_c, chunk]
    deq = q * s_ref[...]                          # scales [S, block_c, 1]
    o_ref[...] = b_ref[...] + deq                 # install = held + deQ(delta)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def dequant_install(q, scales, base, *, block_c: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused dequantize + per-site install for quantized downloads.

    The downlink mirror of :func:`fedagg_dequant`: each site's int8
    broadcast delta (``q`` [S, C, chunk] with per-chunk fp32 ``scales``
    [S, C]) is dequantized and added onto that site's held reference
    ``base`` [S, C, chunk] in ONE pass — the dense fp32 per-site deltas
    never exist in HBM.  Returns the installed models [S, C, chunk];
    installing this result back as the next round's ``base`` is exactly
    the server-side error-feedback recurrence ``held ← held + deQ(Q(g −
    held))``, so downlink quantization errors telescope instead of
    accumulating.
    """
    if interpret is None:
        interpret = jax.default_backend() not in ("tpu", "gpu")
    s, c, chunk = q.shape
    if c == 0:
        return jnp.zeros((s, 0, chunk), jnp.float32)
    block_c = _chunk_block(block_c, s, c, chunk, slabs=3)
    padded = _round_up(c, block_c)
    scales = scales.reshape(s, c, 1)
    if padded != c:
        q = jnp.pad(q, ((0, 0), (0, padded - c), (0, 0)))
        scales = jnp.pad(scales, ((0, 0), (0, padded - c), (0, 0)))
        base = jnp.pad(base, ((0, 0), (0, padded - c), (0, 0)))
    out = pl.pallas_call(
        _dequant_install_kernel,
        grid=(padded // block_c,),
        in_specs=[
            pl.BlockSpec((s, block_c, chunk), lambda i: (0, i, 0)),
            pl.BlockSpec((s, block_c, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((s, block_c, chunk), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((s, block_c, chunk), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, padded, chunk), jnp.float32),
        interpret=interpret,
    )(q, scales, base)
    return out[:, :c] if padded != c else out


def site_block_n(s: int, n: int, block_n: Optional[int] = None,
                 temps: int = 1) -> int:
    """Column block for the [S, N] streaming kernels: the given
    ``block_n``, or the widest lane multiple (≤ 65536) whose double-
    buffered fp32 [S, block_n] slab plus ``temps`` slab-sized
    temporaries fits :data:`VMEM_BUDGET`; never wider than padded N."""
    if block_n is None:
        block_n = fit_block((2 + temps) * s * 4, _LANE, 65536)
    return min(block_n, _round_up(n, _LANE))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fedagg(stacked, weights, *, block_n: Optional[int] = None,
           interpret: Optional[bool] = None):
    """stacked: [S, N] (flattened params); weights: [S] -> [N]."""
    if interpret is None:
        interpret = jax.default_backend() not in ("tpu", "gpu")
    s, n = stacked.shape
    block_n = site_block_n(s, n, block_n)
    padded = _round_up(n, block_n)
    if padded != n:
        stacked = jnp.pad(stacked, ((0, 0), (0, padded - n)))
    out = pl.pallas_call(
        _fedagg_kernel,
        grid=(padded // block_n,),
        in_specs=[
            pl.BlockSpec((s, block_n), lambda i: (0, i)),
            pl.BlockSpec((s, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, padded), stacked.dtype),
        interpret=interpret,
    )(stacked, weights.reshape(s, 1))
    return out[0, :n]
