"""Compile every main-path federation kernel for a TPU v5e chip, with no
chip attached: the installed TPU compiler targets a described ``v5e:2x2``
topology.  This is the compiled (``interpret=False``) lowering the
interpret-mode tests in ``test_kernels.py`` cannot see — tile-illegal
blocks, over-budget VMEM and primitives with no Mosaic lowering are all
refused here.  Shapes are the chip smoke's: the raveled paper-width
SA-Net (11 channels, 24 filters, 4 levels), at S=4 sites (the smoke's
job) and S=32 (the VMEM-fit edge of the S-derived block sizes).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import fedagg as fa
from repro.kernels import quantize as qz
from repro.kernels import robust as rb

N = 6_844_323             # raveled SA-Net params at paper width
CHUNK = 1024              # int8 codec chunk width
C = -(-N // CHUNK)        # chunk rows of the raveled model


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()
    mp.undo()


def _args(kernel, s, dev):
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    flat = sd((s, N), jnp.float32)
    rows = sd((s,), jnp.float32)
    q = sd((s, C, CHUNK), jnp.int8)
    scales = sd((s, C), jnp.float32)
    dense = sd((s, C, CHUNK), jnp.float32)
    return {
        "fedagg": (lambda x, w: fa.fedagg(x, w, interpret=False),
                   (flat, rows)),
        "quantize_int8": (lambda x: qz.quantize_int8(x, interpret=False),
                          (sd((s * C, CHUNK), jnp.float32),)),
        "dequantize_int8": (
            lambda v, sc: qz.dequantize_int8(v, sc, interpret=False),
            (sd((s * C, CHUNK), jnp.int8), sd((s * C,), jnp.float32))),
        "fedagg_dequant": (
            lambda v, sc, u, w: fa.fedagg_dequant(v, sc, u, w,
                                                  interpret=False),
            (q, scales, dense, rows)),
        "dequant_install": (
            lambda v, sc, b: fa.dequant_install(v, sc, b, interpret=False),
            (q, scales, dense)),
        "trimmed_mean": (
            lambda x, a: rb.trimmed_mean(x, a, 1, interpret=False),
            (flat, rows)),
    }[kernel]


@pytest.mark.parametrize("s", [4, 32], ids=lambda s: f"S{s}")
@pytest.mark.parametrize("kernel", [
    "fedagg", "quantize_int8", "dequantize_int8", "fedagg_dequant",
    "dequant_install", "trimmed_mean"])
def test_kernel_compiles_for_v5e(one_chip, kernel, s):
    fn, args = _args(kernel, s, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    hbm = (mem.argument_size_in_bytes + mem.output_size_in_bytes
           + mem.temp_size_in_bytes)
    assert hbm < 16 * 1024 ** 3        # one v5e chip's HBM

