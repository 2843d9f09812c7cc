"""Public op: sub-byte weight GEMM by bit-plane decomposition.

Dispatches to the Pallas kernel or the jnp oracle; both share exact
integer semantics.  Pads K to the 128-lane width and N to the column
block (zero weight values contribute nothing on any bit plane, zero
activation lanes add nothing to any sum — padding is exact).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import lut_gemm_pallas
from .ref import lut_gemm_ref

_LANES = 128


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def lut_gemm(a: jax.Array, w: jax.Array, *, bits: int,
             epilogue: str = "none", shift: int = 0,
             use_pallas: bool = False, interpret: Optional[bool] = None,
             bn: int = 128) -> jax.Array:
    """int8 x int{bits} -> int32 GEMM (optionally fused requant -> int8).

    a: (M, K) int8;  w: (K, N) int8 holding sign-extended b-bit values.
    Bit-identical to ``vta_gemm(a, w, ...)`` — the dense path is the
    differential reference.
    """
    M, K = a.shape
    _, N = w.shape
    if not use_pallas:
        return lut_gemm_ref(a, w, epilogue=epilogue, shift=shift)
    ap = _pad_to(a, 1, _LANES)
    wp = _pad_to(_pad_to(w, 0, _LANES), 1, bn)
    out = lut_gemm_pallas(ap, wp, bits=bits, epilogue=epilogue, shift=shift,
                          bn=bn, interpret=interpret)
    return out[:M, :N]
