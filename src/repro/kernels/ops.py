"""jit'd public wrappers around the Pallas kernels.

On a CPU backend the kernels execute in ``interpret=True`` mode
(the kernel body runs under the Pallas interpreter — bit-faithful to the
TPU program structure); on a real TPU pass ``interpret=False`` (the
default flips on backend detection).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.fedagg import fedagg as _fedagg
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv6


def _default_interpret() -> bool:
    return jax.default_backend() not in ("tpu", "gpu")


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Blocked causal GQA attention. q: [B,Hq,L,D], k/v: [B,Hkv,L,D]."""
    interp = _default_interpret() if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, interpret=interp)


def rwkv6_scan(r, k, v, w, u, *, chunk: int = 128,
               interpret: Optional[bool] = None):
    """RWKV-6 WKV recurrence with VMEM-resident state."""
    interp = _default_interpret() if interpret is None else interpret
    return _rwkv6(r, k, v, w, u, chunk=chunk, interpret=interp)


def fedagg(stacked_params, weights, *, block_n: Optional[int] = None,
           interpret: Optional[bool] = None):
    """Streaming FedAvg aggregation over a [S, N] stacked param matrix."""
    interp = _default_interpret() if interpret is None else interpret
    return _fedagg(stacked_params, weights, block_n=block_n, interpret=interp)


def trimmed_mean(stacked_params, active, f: int, *, block_n: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Byzantine-robust coordinate-wise trimmed mean over the active
    rows of a [S, N] stacked param matrix (see kernels/robust.py)."""
    from repro.kernels.robust import trimmed_mean as _trimmed
    interp = _default_interpret() if interpret is None else interpret
    return _trimmed(stacked_params, active, f, block_n=block_n,
                    interpret=interp)


def masked_median(stacked_params, active, *, block_n: Optional[int] = None,
                  interpret: Optional[bool] = None):
    """Coordinate-wise median over the active rows of [S, N] — the
    trimmed mean at maximal trim depth."""
    from repro.kernels.robust import masked_median as _median
    interp = _default_interpret() if interpret is None else interpret
    return _median(stacked_params, active, block_n=block_n, interpret=interp)


_PYTREE_ENGINES = {}


def fedagg_pytree(stacked_tree, weights, *, interpret: Optional[bool] = None):
    """Eq. 1 over a site-stacked pytree: flatten → one streaming kernel pass
    → unflatten.  Delegates to the AggregationEngine (forced onto the
    Pallas path), which pads the flat buffer to the kernel's block
    multiple and caches the ravel layout."""
    from repro.core.agg_engine import AggregationEngine
    eng = _PYTREE_ENGINES.get(interpret)
    if eng is None:
        eng = _PYTREE_ENGINES.setdefault(
            interpret, AggregationEngine(use_pallas=True, interpret=interpret))
    return eng.global_mean(stacked_tree, weights)


def fedagg_dequant(q, scales, u, weights, *, block_c: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """Fused dequantize + Eq. 1 weighted fold over int8 site deltas
    ([S, C, chunk] values + [S, C] scales), also emitting the next
    error-feedback residual ``u − deq`` — the compressed round engine's
    one-pass server step (see ``repro.core.round_engine``)."""
    from repro.kernels.fedagg import fedagg_dequant as _fused
    interp = _default_interpret() if interpret is None else interpret
    return _fused(q, scales, u, weights, block_c=block_c, interpret=interp)


def dequant_install(q, scales, base, *, block_c: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused dequantize + install for quantized broadcast deltas
    ([S, C, chunk] int8 values + [S, C] scales + [S, C, chunk] held
    references) → the per-site installed models ``base + deQ(q)`` — the
    downlink mirror of :func:`fedagg_dequant` (see
    ``repro.core.round_engine``'s bidirectional compressed scan)."""
    from repro.kernels.fedagg import dequant_install as _install
    interp = _default_interpret() if interpret is None else interpret
    return _install(q, scales, base, block_c=block_c, interpret=interp)


def quantize_int8(x2d, *, block_c: int = 256, interpret: Optional[bool] = None):
    """Per-chunk int8 quantization: [C, chunk] fp32 → (int8 [C, chunk],
    fp32 scales [C]).  The upload-compression hot path (see
    ``repro.comms.compression``)."""
    from repro.kernels.quantize import quantize_int8 as _quant
    interp = _default_interpret() if interpret is None else interpret
    return _quant(x2d, block_c=block_c, interpret=interp)


def dequantize_int8(values, scales, *, block_c: int = 256,
                    interpret: Optional[bool] = None):
    """Inverse of :func:`quantize_int8`: int8 values × per-chunk scales."""
    from repro.kernels.quantize import dequantize_int8 as _dequant
    interp = _default_interpret() if interpret is None else interpret
    return _dequant(values, scales, block_c=block_c, interpret=interp)


def mamba_scan(dt, b_mat, c_mat, x, log_a, *, chunk: int = 128,
               block_di: int = 512, interpret: Optional[bool] = None):
    """Mamba selective scan with VMEM-resident state (see mamba_scan.py)."""
    from repro.kernels.mamba_scan import mamba_scan as _mamba
    interp = _default_interpret() if interpret is None else interpret
    return _mamba(dt, b_mat, c_mat, x, log_a, chunk=chunk,
                  block_di=block_di, interpret=interp)
