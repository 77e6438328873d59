"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode;
the compiled TPU lowering is covered by tests/test_tpu_compile.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import fedagg_ref, flash_attention_ref, rwkv6_scan_ref

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("b,hq,hkv,l,d", [
    (1, 2, 1, 128, 64), (2, 4, 2, 256, 32), (1, 8, 8, 128, 64),
    (1, 6, 3, 384, 64), (2, 1, 1, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(b, hq, hkv, l, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, l, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, l, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, l, d), dtype)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)
    assert out.dtype == dtype


@pytest.mark.parametrize("window", [32, 100, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 32))
    k = jax.random.normal(ks[1], (1, 2, 256, 32))
    v = jax.random.normal(ks[2], (1, 2, 256, 32))
    out = ops.flash_attention(q, k, v, window=window, block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    out = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,h,l,d,chunk", [
    (1, 2, 64, 16, 16), (2, 4, 128, 32, 32), (1, 1, 256, 64, 128),
    (1, 2, 96, 64, 96),
])
def test_rwkv6_scan_shapes(b, h, l, d, chunk):
    ks = jax.random.split(KEY, 5)
    r, k, v = [jax.random.normal(kk, (b, h, l, d)) for kk in ks[:3]]
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, l, d))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (h, d)) * 0.1
    out = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk)
    ref, _ = rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)


def test_rwkv6_scan_bf16():
    ks = jax.random.split(KEY, 5)
    r, k, v = [jax.random.normal(kk, (1, 2, 64, 32), jnp.bfloat16) for kk in ks[:3]]
    w = (jax.nn.sigmoid(jax.random.normal(ks[3], (1, 2, 64, 32))) * 0.5 + 0.45
         ).astype(jnp.bfloat16)
    u = (jax.random.normal(ks[4], (2, 32)) * 0.1).astype(jnp.bfloat16)
    out = ops.rwkv6_scan(r, k, v, w, u, chunk=32)
    ref, _ = rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=0.05, atol=0.05)


@pytest.mark.parametrize("s,n,block", [(4, 1024, 256), (8, 4096, 4096),
                                       (16, 512, 512), (2, 65536, 65536)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedagg_sweep(s, n, block, dtype):
    x = jax.random.normal(KEY, (s, n), dtype)
    w = jax.nn.softmax(jax.random.normal(KEY, (s,)))
    out = ops.fedagg(x, w, block_n=block)
    ref = fedagg_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("s,c,chunk,block_c", [(3, 7, 256, 4), (4, 16, 128, 16),
                                               (2, 1, 128, 32)])
def test_fedagg_dequant_fuses_decode_and_fold(s, c, chunk, block_c):
    """The compressed round engine's one-pass server step: dequantize +
    Eq. 1 fold + error-feedback residual, vs the separate numpy codec."""
    from repro.comms.compression import MIN_SCALE
    rng = np.random.default_rng(7)
    u = (rng.normal(size=(s, c, chunk)) * 0.1).astype(np.float32)
    w = rng.dirichlet(np.ones(s)).astype(np.float32)
    scale = np.maximum(np.max(np.abs(u), axis=-1) / 127.0,
                       MIN_SCALE).astype(np.float32)
    q = np.clip(np.rint(u / scale[..., None]), -127, 127).astype(np.int8)
    deq = q.astype(np.float32) * scale[..., None]
    g, r = ops.fedagg_dequant(jnp.asarray(q), jnp.asarray(scale),
                              jnp.asarray(u), jnp.asarray(w),
                              block_c=block_c)
    np.testing.assert_allclose(np.asarray(g), np.einsum("s,sct->ct", w, deq),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(r), u - deq, rtol=1e-5, atol=1e-7)


def test_fedagg_dequant_matches_jnp_quantize_path():
    """Kernel quantize → fused fold agrees with the traced jnp twin the
    CPU engine path uses (quantize_dequantize_ref + einsum fold)."""
    from repro.kernels.quantize import quantize_dequantize_ref
    rng = np.random.default_rng(8)
    u = jnp.asarray((rng.normal(size=(4, 5, 128)) * 0.02).astype(np.float32))
    w = jnp.asarray(rng.dirichlet(np.ones(4)).astype(np.float32))
    q, sc = ops.quantize_int8(u.reshape(20, 128))
    g, _ = ops.fedagg_dequant(q.reshape(4, 5, 128), sc.reshape(4, 5), u, w)
    deq = quantize_dequantize_ref(u)
    np.testing.assert_allclose(np.asarray(g),
                               np.einsum("s,sct->ct", np.asarray(w),
                                         np.asarray(deq)),
                               rtol=1e-6, atol=1e-7)


def test_fedagg_pytree_matches_eq1():
    """Kernel aggregation == Eq. 1 weighted mean on a realistic param tree."""
    from repro.core.stacking import weighted_mean
    key = jax.random.PRNGKey(3)
    tree = {"layer": {"w": jax.random.normal(key, (8, 32, 48)),
                      "b": jax.random.normal(key, (8, 48))},
            "head": jax.random.normal(key, (8, 48, 100))}
    w = jnp.asarray(np.random.default_rng(0).dirichlet(np.ones(8)), jnp.float32)
    out = ops.fedagg_pytree(tree, w)
    ref = weighted_mean(tree, w)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s,n,f,block", [
    (4, 1024, 1, 256), (8, 4096, 2, 4096), (5, 512, 2, 512),
    (16, 300, 5, 512), (3, 65536, 1, 65536),
])
def test_trimmed_mean_kernel_matches_ref(s, n, f, block):
    """Pallas coordinate-wise trimmed mean == the jnp twin, bit-exact
    (same op sequence per block), with every row active."""
    from repro.kernels.robust import trimmed_mean_ref
    x = jax.random.normal(KEY, (s, n), jnp.float32) * 3.0
    active = jnp.ones((s,), bool)
    out = ops.trimmed_mean(x, active, f, block_n=block)
    ref = trimmed_mean_ref(x, active, f)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("s,n,block", [(4, 1024, 256), (7, 2048, 2048),
                                       (9, 513, 1024)])
def test_masked_median_kernel_matches_ref_and_numpy(s, n, block):
    """Pallas masked median == jnp twin bit-exact, and == np.median on
    the active rows (the trim-at-max-depth construction is a real
    median for odd AND even active counts)."""
    from repro.kernels.robust import masked_median_ref
    x = jax.random.normal(KEY, (s, n), jnp.float32) * 2.0
    mask = np.ones(s, bool)
    mask[:: max(s // 2, 1)] = True          # keep all, then drop one row
    mask[s - 1] = False
    active = jnp.asarray(mask)
    out = ops.masked_median(x, active, block_n=block)
    ref = masked_median_ref(x, active)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np_med = np.median(np.asarray(x)[mask], axis=0)
    np.testing.assert_allclose(np.asarray(out), np_med, rtol=1e-6, atol=1e-6)


def test_trimmed_mean_kernel_masked_rows():
    """Inactive rows are invisible: trimming over a masked [S, N] buffer
    equals trimming the compacted active-only buffer."""
    from repro.kernels.robust import trimmed_mean_ref
    s, n, f = 8, 768, 1
    x = jax.random.normal(KEY, (s, n), jnp.float32)
    mask = np.array([1, 0, 1, 1, 0, 1, 1, 1], bool)
    out = ops.trimmed_mean(x, jnp.asarray(mask), f)
    compact = ops.trimmed_mean(jnp.asarray(np.asarray(x)[mask]),
                               jnp.ones(int(mask.sum()), bool), f)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(compact))
    ref = trimmed_mean_ref(x, jnp.asarray(mask), f)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("b,l,di,ds,chunk,blk", [
    (1, 64, 32, 8, 16, 16), (2, 128, 64, 16, 64, 32), (1, 96, 48, 8, 96, 48),
])
def test_mamba_scan_kernel(b, l, di, ds, chunk, blk):
    from repro.kernels.ref import mamba_scan_ref
    ks = jax.random.split(KEY, 4)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, l, di)) - 1.0)
    b_mat = jax.random.normal(ks[1], (b, l, ds))
    c_mat = jax.random.normal(ks[2], (b, l, ds))
    x = jax.random.normal(ks[3], (b, l, di))
    log_a = jnp.log(jnp.broadcast_to(jnp.arange(1.0, ds + 1.0), (di, ds)))
    out = ops.mamba_scan(dt, b_mat, c_mat, x, log_a, chunk=chunk, block_di=blk)
    ref, _ = mamba_scan_ref(dt, b_mat, c_mat, x, log_a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
