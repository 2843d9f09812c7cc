"""Sub-byte weight path: packed storage, LUT-GEMM kernel, quantize fixes.

Covers the lowbit tentpole end to end — layout pack/unpack round trips
(int4/int2/int1, odd widths, padding tails), packed TensorMeta storage
through both engines, the LUT-GEMM kernel vs the dense GEMM, per-shape
kernel selection, the VtaLinear bits= knob — plus failing-before /
passing-after regressions for the three quantize.py bugs the path sits
on top of (hard-coded int8 clip, overflow-before-clip, empty-input
percentile crash).
"""
from unittest import mock

import numpy as np
import pytest

from repro.core import hwspec, layout
from repro.core import quantize as q
from repro.core.backend import PallasBackend, SimulatorBackend
from repro.core.program import Program, TensorMeta
from repro.core.scheduler import Epilogue

RNG = np.random.default_rng(20260808)


# ----------------------------------------------------------------------
# layout: bit-packing round trips
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bits", [1, 2, 4])
@pytest.mark.parametrize("shape", [(1,), (7,), (8,), (9,), (3, 5),
                                   (2, 16), (4, 31), (2, 3, 13)])
def test_pack_bits_roundtrip(bits, shape):
    qmin, qmax = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    a = RNG.integers(qmin, qmax + 1, size=shape).astype(np.int8)
    packed = layout.pack_bits(a, bits)
    assert packed.dtype == np.uint8
    ppb = 8 // bits
    assert packed.shape[-1] == -(-shape[-1] // ppb)
    out = layout.unpack_bits(packed, bits, shape[-1])
    np.testing.assert_array_equal(out, a)


@pytest.mark.parametrize("bits", [1, 2, 4])
def test_pack_bits_extremes_and_tail(bits):
    """Boundary values survive sign extension; the padding tail decodes
    as zeros and is dropped."""
    qmin, qmax = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    a = np.array([qmin, qmax, 0, -1] * 3 + [qmin], np.int8)  # odd length
    packed = layout.pack_bits(a, bits)
    np.testing.assert_array_equal(layout.unpack_bits(packed, bits, a.size), a)
    # the tail bits beyond a.size are zero fields
    full = layout.unpack_bits(packed, bits, packed.size * (8 // bits))
    assert (full[a.size:] == 0).all()


def test_pack_bits_rejects_out_of_range():
    with pytest.raises(ValueError, match="outside int4 range"):
        layout.pack_bits(np.array([8], np.int8), 4)
    with pytest.raises(ValueError, match="outside int2 range"):
        layout.pack_bits(np.array([-3], np.int8), 2)


@pytest.mark.parametrize("bits", [1, 2, 4])
def test_pack_wgt_elems_roundtrip(bits):
    qmin, qmax = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    blocked = RNG.integers(qmin, qmax + 1, size=(3, 2, 16, 16)).astype(np.int8)
    packed = layout.pack_wgt_elems(blocked, bits)
    assert packed.shape == (3, 2, 16 * 16 * bits // 8)
    out = layout.unpack_wgt_elems(packed, bits, 16, 16)
    np.testing.assert_array_equal(out, blocked)


@pytest.mark.parametrize("bits", [1, 2, 4])
@pytest.mark.parametrize("kind,shape", [("wgt", (19, 37)),
                                        ("cwgt", (5, 9, 3, 3))])
def test_tensormeta_packed_roundtrip(bits, kind, shape):
    """Weight metas on a sub-byte spec store uint8 packed bytes (8/bits
    smaller) and unpack back to the exact logical tensor — including
    non-multiple-of-block shapes whose padding lives inside the packed
    elements."""
    spec = hwspec.lowbit(bits)
    qmin, qmax = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    meta = TensorMeta(kind=kind, shape=shape, dtype="int8")
    w = RNG.integers(qmin, qmax + 1, size=shape).astype(np.int8)
    packed = meta.pack(w, spec)
    assert packed.dtype == np.uint8
    spec8 = hwspec.pynq()
    assert meta.nbytes(spec) * 8 == meta.nbytes(spec8) * bits
    assert meta.elem_bytes(spec) == spec.wgt_elem_bytes
    np.testing.assert_array_equal(meta.unpack(packed, spec), w)


def test_pack_rejects_weights_wider_than_spec():
    """int8-valued weights on an int4 spec fail loudly instead of
    silently corrupting the packed image."""
    spec = hwspec.lowbit(4)
    meta = TensorMeta(kind="wgt", shape=(16, 16), dtype="int8")
    w = np.full((16, 16), 100, np.int8)
    with pytest.raises(ValueError, match="outside int4 range"):
        meta.pack(w, spec)


def test_hwspec_validates_wgt_bits():
    with pytest.raises(ValueError, match="wgt_bits"):
        hwspec.pynq().replace(wgt_bits=3)
    # lowbit keeps the WGT SRAM depth (and so the uop budget) fixed
    for bits in (1, 2, 4):
        s = hwspec.lowbit(bits)
        assert s.wgt_packed
        assert s.wgt_depth == hwspec.pynq().wgt_depth
        assert s.wgt_elem_bytes == hwspec.pynq().wgt_elem_bytes * bits // 8


# ----------------------------------------------------------------------
# quantize.py regressions (each failed before its PR-8 fix)
# ----------------------------------------------------------------------
def test_quantize_per_channel_respects_bits():
    """Regression: quantize_per_channel hard-coded np.clip(q, -128, 127),
    so values beyond the calibrated range came back outside the int4
    range (silent int8-range saturation) and the packed path rejects
    them.  With bits=4 the clip lands on the correct qmin/qmax."""
    w = RNG.normal(size=(8, 32)).astype(np.float32)
    scales = q.per_channel_scales(w, axis=0, bits=4)
    # production weights drift past the calibration range (3x outliers):
    # before the fix these quantized to ~21, inside [-128, 127] but far
    # outside int4
    q4 = q.quantize_per_channel(3.0 * w, scales, axis=0, bits=4)
    assert q4.dtype == np.int8
    assert q4.min() >= -8 and q4.max() <= 7
    # and the in-range round trip is unaffected
    q4_in = q.quantize_per_channel(w, scales, axis=0, bits=4)
    np.testing.assert_allclose(
        q4_in.astype(np.float64) * scales.astype(np.float64)[:, None],
        w, atol=float(scales.max()))
    # int4 quantized values feed the packed layout without a range error
    layout.pack_bits(q4, 4)


def test_quantize_bias_clips_before_the_cast():
    """Regression: np.round(...).astype(np.int64).clip(...) — a float64
    beyond int64 range overflows IN THE CAST (wrapping to INT64_MIN),
    so a huge positive bias came back as -2^31 instead of saturating at
    +2^31-1.  The clip must happen in the float domain."""
    bias = np.array([1.0, -1.0, 0.5], np.float64)
    with np.errstate(invalid="ignore"):
        out = q.quantize_bias(bias, sx=1e-20, sw=1e-20)  # ratio ~ 1e40
    assert out.dtype == np.int32
    assert out[0] == (1 << 31) - 1          # saturates, keeps its sign
    assert out[1] == -(1 << 31)
    assert out[2] == (1 << 31) - 1
    # sane ratios are untouched
    np.testing.assert_array_equal(
        q.quantize_bias(np.array([2.0, -3.0]), sx=0.5, sw=0.5),
        np.array([8, -12], np.int32))


def test_calibrate_empty_input_both_branches():
    """Regression: the max branch was guarded by a.max(initial=0.0) but
    the percentile branch crashed on size-0 input."""
    empty = np.zeros((0, 4), np.float32)
    qp_max = q.calibrate(empty)                      # was already safe
    qp_pct = q.calibrate(empty, percentile=99.0)     # used to raise
    assert qp_max.scale > 0 and qp_pct.scale > 0
    assert qp_max.scale == qp_pct.scale
    # non-empty percentile path still calibrates below the max
    x = np.concatenate([np.ones(99), [100.0]])
    assert q.calibrate(x, percentile=90.0).scale < q.calibrate(x).scale


# ----------------------------------------------------------------------
# LUT-GEMM kernel vs the dense GEMM (bit-exact by construction)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bits", [1, 2, 4])
@pytest.mark.parametrize("shape", [(1, 32, 16), (4, 144, 130),
                                   (18, 96, 64)])
def test_lut_gemm_matches_dense(bits, shape):
    import jax.numpy as jnp

    from repro.kernels.lut_gemm import lut_gemm
    from repro.kernels.vta_gemm import vta_gemm

    qmin, qmax = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    M, K, N = shape
    a = RNG.integers(-128, 128, size=(M, K)).astype(np.int8)
    w = RNG.integers(qmin, qmax + 1, size=(K, N)).astype(np.int8)
    for ep, sh in [("none", 0), ("requant", 5)]:
        got = np.asarray(lut_gemm(
            jnp.asarray(a), jnp.asarray(w), bits=bits,
            epilogue=ep, shift=sh, use_pallas=True))
        want = np.asarray(vta_gemm(jnp.asarray(a), jnp.asarray(w),
                                   epilogue=ep, shift=sh))
        np.testing.assert_array_equal(
            got, want, err_msg=f"bits={bits} shape={shape} ep={ep}")


def test_lut_gemm_ref_is_dense():
    import jax.numpy as jnp

    from repro.kernels.lut_gemm import lut_gemm
    a = RNG.integers(-128, 128, size=(3, 32)).astype(np.int8)
    w = RNG.integers(-8, 8, size=(32, 16)).astype(np.int8)
    got = np.asarray(lut_gemm(jnp.asarray(a), jnp.asarray(w), bits=4))
    np.testing.assert_array_equal(
        got, a.astype(np.int64) @ w.astype(np.int64))


# ----------------------------------------------------------------------
# end-to-end: packed programs on both engines
# ----------------------------------------------------------------------
def _matmul_program(spec, w, m):
    p = Program(spec)
    x = p.input("x", (m, w.shape[1]))
    c = p.matmul(x, p.constant("w", w), epilogue=Epilogue(shift=5),
                 name="mm")
    p.output(c)
    return p.compile(use_cache=False)


@pytest.mark.parametrize("bits", [1, 2, 4])
def test_packed_program_bit_exact_both_engines(bits):
    spec = hwspec.lowbit(bits)
    qmin, qmax = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    w = RNG.integers(qmin, qmax + 1, size=(56, 72)).astype(np.int8)
    x = RNG.integers(-128, 128, size=(5, 72)).astype(np.int8)
    want = np.clip((x.astype(np.int64) @ w.T.astype(np.int64)) >> 5,
                   -128, 127).astype(np.int8)
    compiled = _matmul_program(spec, w, 5)
    for be in (SimulatorBackend(), PallasBackend()):
        got = compiled(backend=be, x=x)
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"bits={bits} {be.name}")


def test_packed_constants_shrink_dram():
    """The acceptance bar: staged constant-weight bytes shrink >= 2x at
    int4 (8/bits in general), and the whole DRAM image is smaller, so
    DevicePool trimmed clones get proportionally cheaper."""
    c8 = _matmul_program(
        hwspec.pynq(),
        RNG.integers(-128, 128, size=(128, 256)).astype(np.int8), 4)
    sizes = {8: c8.const_bytes}
    for bits in (4, 2, 1):
        qmin, qmax = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
        w = RNG.integers(qmin, qmax + 1, size=(128, 256)).astype(np.int8)
        cb = _matmul_program(hwspec.lowbit(bits), w, 4)
        sizes[bits] = cb.const_bytes
        assert cb.const_bytes * 8 == c8.const_bytes * bits
        assert f"wgt int{bits} packed" in cb.describe()
        assert cb.device.dram._next < c8.device.dram._next
    assert sizes[4] * 2 == sizes[8]          # >= 2x at int4


def test_lut_selected_for_decode_shapes_only():
    """Per-shape kernel selection: decode-shaped (few-row) launches on a
    sub-byte spec route through the LUT kernel; a row bound of 0 sends
    them to the dense kernel; int8 specs never use it."""
    spec = hwspec.lowbit(4)
    w = RNG.integers(-8, 8, size=(128, 128)).astype(np.int8)
    x = RNG.integers(-128, 128, size=(2, 128)).astype(np.int8)
    compiled = _matmul_program(spec, w, 2)
    want = np.clip((x.astype(np.int64) @ w.T.astype(np.int64)) >> 5,
                   -128, 127).astype(np.int8)

    got = compiled(backend=PallasBackend(), x=x)
    np.testing.assert_array_equal(got, want)
    assert sum(s.lut_launches for s in compiled.last_stats) >= 1

    with mock.patch.object(PallasBackend, "LUT_MAX_ROWS", 0):
        got = compiled(backend=PallasBackend(), x=x)
    np.testing.assert_array_equal(got, want)
    assert sum(s.lut_launches for s in compiled.last_stats) == 0

    # int8 spec: auto never selects the LUT kernel
    c8 = _matmul_program(hwspec.pynq(), w, 2)
    c8(backend=PallasBackend(), x=x)
    assert sum(s.lut_launches for s in c8.last_stats) == 0


def test_persistent_image_roundtrip_packed():
    """Persistent-image save/restore moves RAW packed bytes (the session
    state contract is storage-level, not logical-level)."""
    spec = hwspec.lowbit(4)
    w = RNG.integers(-8, 8, size=(32, 32)).astype(np.int8)
    compiled = _matmul_program(spec, w, 2)
    nid = compiled.input_ids["w"]
    got = compiled._read(nid)
    np.testing.assert_array_equal(got, w)


# ----------------------------------------------------------------------
# VtaLinear bits= knob
# ----------------------------------------------------------------------
def test_vta_linear_int4():
    from repro.models.quantized import VtaLinear

    rng = np.random.default_rng(7)
    w = rng.normal(size=(96, 80)).astype(np.float32) * 0.1
    x = rng.normal(size=(2, 96)).astype(np.float32)

    lin4 = VtaLinear(w, bits=4)
    assert lin4.spec.wgt_bits == 4
    assert lin4.w_q.min() >= -8 and lin4.w_q.max() <= 7
    y4 = lin4(x)
    # both engines agree bit-exactly on the quantized program, so the
    # dequantized outputs match exactly too
    y4_sim = lin4(x, backend=SimulatorBackend())
    np.testing.assert_array_equal(y4, y4_sim)
    # int4 output tracks the int8 path's dequant reference within the
    # coarser quantization error (16x fewer levels)
    y8 = VtaLinear(w, bits=8)(x)
    ref = x @ w
    err4 = np.abs(y4 - ref).max()
    err8 = np.abs(y8 - ref).max()
    assert err4 < 16 * max(err8, 1e-3) + 0.5
    # the compiled program stages packed constants at half the int8 size
    compiled = next(iter(lin4._programs.values()))
    assert "wgt int4 packed" in compiled.describe()
    lin8 = VtaLinear(w, bits=8)
    lin8(x)
    c8 = next(iter(lin8._programs.values()))
    assert compiled.const_bytes * 2 == c8.const_bytes
