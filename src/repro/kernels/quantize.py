"""Pallas TPU kernels for per-chunk int8 quantize / dequantize.

The communication bottleneck of federated rounds is upload bandwidth
(one full model per site per round), so site deltas are quantized before
they hit the wire (see ``repro.comms.compression``).  On an accelerator
the quantize step is purely memory-bound — one pass over the [C, chunk]
delta buffer computing a per-chunk absmax scale and the rounded int8
values — so, like ``fedagg``, the kernel's job is to stream HBM once:

  grid = (ceil(C / block_c)); each cell loads a [block_c, chunk] slab
  into VMEM, reduces |x| along the chunk axis on the VPU for the scales,
  and writes the int8 values and fp32 scales exactly once.  Inside the
  kernels the scales are a [C, 1] column (a 1-D block would not match
  XLA's 1-D tiling); the public signatures keep them 1-D [C], so the
  wire format (``QuantizedTensor``) is unchanged.

``chunk`` is the quantization granularity (one fp32 scale per chunk);
keep it a multiple of 128 so compiled blocks tile the lane width
cleanly.  ``interpret`` defaults to compiled on TPU/GPU and to the
Pallas interpreter elsewhere — the same dispatch as ``fedagg``; the
numpy reference lives in ``repro.comms.compression`` and the two are
tested to agree exactly (both round half-to-even).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the ONE scale floor, shared with the numpy encoder so both backends
# stay bit-exact (comms.compression has no module-level kernel imports,
# so this cross-layer import cannot cycle)
from repro.comms.compression import MIN_SCALE
_QMAX = 127.0


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def quantize_dequantize_ref(mat):
    """Pure-jnp int8 quantize→dequantize round trip over [..., C, chunk]
    fp32 — the traced CPU counterpart of the kernel pair, used by the
    compiled round engine where the quantized values never leave the
    device.  Same math as the kernel and the numpy encoder (absmax/127
    scales with the shared ``MIN_SCALE`` floor, half-to-even rounding),
    so all three backends agree bit-exactly.
    """
    scale = jnp.maximum(jnp.max(jnp.abs(mat), axis=-1) / _QMAX, MIN_SCALE)
    q = jnp.clip(jnp.round(mat / scale[..., None]), -_QMAX, _QMAX)
    return q * scale[..., None]


def quantize_dequantize_fp8_ref(mat):
    """Traced float8_e4m3 quantize→dequantize round trip (absmax→448
    per-chunk scaling, RTNE cast) — mirrors ``Fp8Codec`` on device."""
    scale = jnp.maximum(jnp.max(jnp.abs(mat), axis=-1) / 448.0, MIN_SCALE)
    q = (mat / scale[..., None]).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * scale[..., None]


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                    # [block_c, chunk]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True) / _QMAX,
                        MIN_SCALE)                        # [block_c, 1]
    q = jnp.round(x / scale)                              # half-to-even, VPU
    q_ref[...] = jnp.clip(q, -_QMAX, _QMAX).astype(jnp.int8)
    s_ref[...] = scale


def _dequantize_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)                    # [block_c, chunk]
    o_ref[...] = q * s_ref[...]                           # scales [block_c, 1]


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def quantize_int8(x, *, block_c: int = 256,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [C, chunk] fp32 → (values int8 [C, chunk], scales fp32 [C])."""
    if interpret is None:
        interpret = jax.default_backend() not in ("tpu", "gpu")
    c, chunk = x.shape
    if c == 0:                               # empty leaf: nothing to quantize
        return (jnp.zeros((0, chunk), jnp.int8), jnp.zeros((0,), jnp.float32))
    block_c = min(block_c, c)
    padded = _round_up(c, block_c)
    if padded != c:
        x = jnp.pad(x, ((0, padded - c), (0, 0)))
    q, s = pl.pallas_call(
        _quantize_kernel,
        grid=(padded // block_c,),
        in_specs=[pl.BlockSpec((block_c, chunk), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_c, chunk), lambda i: (i, 0)),
            pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, chunk), jnp.int8),
            jax.ShapeDtypeStruct((padded, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q[:c], s[:c, 0]


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def dequantize_int8(q, s, *, block_c: int = 256,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """values int8 [C, chunk] + scales fp32 [C] → fp32 [C, chunk]."""
    if interpret is None:
        interpret = jax.default_backend() not in ("tpu", "gpu")
    c, chunk = q.shape
    if c == 0:
        return jnp.zeros((0, chunk), jnp.float32)
    block_c = min(block_c, c)
    padded = _round_up(c, block_c)
    s = s.reshape(c, 1)
    if padded != c:
        q = jnp.pad(q, ((0, padded - c), (0, 0)))
        s = jnp.pad(s, ((0, padded - c), (0, 0)))
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(padded // block_c,),
        in_specs=[
            pl.BlockSpec((block_c, chunk), lambda i: (i, 0)),
            pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, chunk), jnp.float32),
        interpret=interpret,
    )(q, s)
    return out[:c] if padded != c else out
