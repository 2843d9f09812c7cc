"""The main path's Pallas kernels compile for a TPU v5e chip.

The CPU suite runs every kernel in the Pallas interpreter, which accepts
programs Mosaic refuses (an int32 matmul, a 3-D gather, a block larger
than the 16 MiB of scoped VMEM).  These tests compile each kernel with
``interpret=False`` for one chip of a *described* v5e topology — no chip
needed — at the shapes the engine launches on the chip, and check that
the kernel really is a Mosaic custom call in the compiled program.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
every test worker imports every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.lut_gemm.kernel import lut_gemm_pallas
from repro.kernels.tensor_alu.kernel import tensor_alu_pallas
from repro.kernels.vta_gemm.kernel import vta_gemm_pallas
from repro.models.vta_decoder import DecoderConfig


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back without one:
        # keep such entries out of any persistent cache the environment
        # configured
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap8"])
@pytest.mark.parametrize("epilogue", ["none", "requant"])
def test_vta_gemm_compiles(one_chip, epilogue, vmapped):
    """int8 x int8 -> int32 on the MXU, at a ResNet C2-like reduction depth
    (9 taps x 128 channels), plain and as the engine's batched launch of 8
    tiles."""
    fn = functools.partial(vta_gemm_pallas, epilogue=epilogue, shift=6,
                           interpret=False)
    lead = (8,) if vmapped else ()
    if vmapped:
        fn = jax.vmap(fn)
    text = _compile_text(fn, _sds(one_chip, lead + (128, 1152), jnp.int8),
                         _sds(one_chip, lead + (1152, 256), jnp.int8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [(16384, 128), (32, 57344)],
                         ids=["tall", "wide"])
def test_tensor_alu_compiles(one_chip, shape):
    """The largest row-stacked epilogues the smoke's tpu_like C2-C12
    programs hand the ALU (C2: 16384 x 128; C2's wide tile batch:
    32 x 57344), with a tensor source, stay within scoped VMEM because the
    kernel blocks both axes."""
    fn = functools.partial(
        tensor_alu_pallas,
        chain=(("add", None), ("shr", 6), ("max", 0), ("min", 127)),
        interpret=False)
    text = _compile_text(fn, _sds(one_chip, shape, jnp.int32),
                         _sds(one_chip, shape, jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits", [4, 2, 1])
def test_lut_gemm_compiles(one_chip, bits):
    """Decode-shaped sub-byte GEMM as the engine launches it (rows padded
    to 128, K = 1024)."""
    fn = functools.partial(lut_gemm_pallas, bits=bits, epilogue="requant",
                           shift=8, interpret=False)
    text = _compile_text(fn, _sds(one_chip, (128, 1024), jnp.int8),
                         _sds(one_chip, (1024, 256), jnp.int8))
    assert "tpu_custom_call" in text


def test_decode_attention_compiles(one_chip):
    """One decode step at the QuantDecoder's shapes: B=1, one query head
    per KV head, head_dim = d_model / n_heads, the padded s_max cache."""
    cfg = DecoderConfig()
    H, D, S = cfg.n_heads, cfg.head_dim, cfg.s_max
    fn = functools.partial(decode_attention_pallas, interpret=False)
    text = _compile_text(fn, _sds(one_chip, (H, 1, D), jnp.float32),
                         _sds(one_chip, (H, S, D), jnp.float32),
                         _sds(one_chip, (H, S, D), jnp.float32),
                         _sds(one_chip, (1,), jnp.int32))
    assert "tpu_custom_call" in text
