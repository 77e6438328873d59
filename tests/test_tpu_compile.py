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

The last test compiles a tiny SA-Net's federated round and reads the
convolutions the compiler made of its site update.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
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


# ---------------------------------------------------------------------------
# The site update's convolutions, as the TPU compiler builds them
# ---------------------------------------------------------------------------


def _computations(hlo: str):
    """``{name: body}`` of every computation in an HLO module's text."""
    out, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return {k: "\n".join(v) for k, v in out.items()}


def _site_windows(hlo: str, s: int):
    """Convolutions whose window runs over the site axis: a fourth window
    dimension of size ``s`` with ``lhs_dilate`` ``s`` (a site-grouped
    convolution's forward or input gradient)."""
    out = []
    for line in hlo.splitlines():
        if " convolution(" not in line:
            continue
        size = re.search(r"window=\{[^}]*size=(\S+)", line).group(1)
        lhs = re.search(r"window=\{[^}]*lhs_dilate=([\dx]+)", line)
        dims, dil = size.split("x"), lhs.group(1).split("x") if lhs else []
        if len(dims) == 4 and dims[3] == str(s) and dil[3:] == [str(s)]:
            out.append(line)
    return out


def _iota_masked_convs(hlo: str):
    """Computations holding a convolution that reach an ``s32`` iota
    (a site-grouped kernel gradient: the output gradient broadcast over
    the sites and masked by an iota compare)."""
    comps = _computations(hlo)
    out = []
    for name, body in comps.items():
        if " convolution(" not in body:
            continue
        seen, todo = set(), [name]
        while todo:
            c = todo.pop()
            if c in seen or c not in comps:
                continue
            seen.add(c)
            todo += re.findall(r"calls=%([\w.\-]+)", comps[c])
        if any(re.search(r"= s32\[[^\]]*\]\S* iota\(", comps[c])
               for c in seen):
            out.append(name)
    return out


@pytest.mark.parametrize("form", ["chosen", "vmap"])
def test_site_update_convs_are_plain(one_chip, form, monkeypatch):
    """A tiny SA-Net (8^3, 8 filters, 2 levels) over 4 sites, one round
    (default precision: the windows do not depend on it, and the compile
    is several times faster than at ``"highest"``): the site update the program
    chooses holds only plain 3-D windows.  Forcing the vmap shows what the guards
    catch: site-axis windows and iota-masked kernel gradients.  A
    ``lhs_dilate=2x2x2`` window (the stride-2 convolutions' input
    gradient) is the model's own and passes."""
    from repro.api import FederatedJob, TaskConfig
    from repro.core import federation
    if form == "vmap":
        monkeypatch.setattr(federation, "_has_conv", lambda jaxpr: False)
    s = 4
    job = FederatedJob(task=TaskConfig(kind="dose", sites=s, batch=1,
                                       volume=(8, 8, 8), base_filters=8,
                                       num_levels=2, seed=0), rounds=1)
    bundle = job.task.build()
    ctx = job.context(bundle)

    def sd(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype, sharding=one_chip), tree)

    state = jax.eval_shape(lambda: federation.init_fl_state(
        ctx, bundle.init_fn, jax.random.PRNGKey(0)))
    args = (sd(state), sd(bundle.stacked(0, 1)),
            sd(federation.make_round_inputs(ctx)))
    hlo = jax.jit(federation.build_fl_round(ctx)).lower(
        *args).compile().as_text()
    convs = [ln for ln in hlo.splitlines() if " convolution(" in ln]
    assert convs
    if form == "chosen":
        assert _site_windows(hlo, s) == []
        assert _iota_masked_convs(hlo) == []
        assert any("lhs_dilate=2x2x2 " in ln for ln in convs)
    else:
        assert _site_windows(hlo, s)
        assert _iota_masked_convs(hlo)
