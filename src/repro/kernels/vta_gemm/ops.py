"""Public op: quantized GEMM through the VTA datapath.

Dispatches to the Pallas kernel on TPU and the jnp oracle elsewhere; both
share exact integer semantics, so tests sweep shapes/dtypes against ref.
Handles padding to block multiples (the runtime's job on the FPGA: VTA's
2D DMA pads tiles on the fly; here we pad once at the XLA level).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import vta_gemm_pallas
from .ref import vta_gemm_ref


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def vta_gemm(a: jax.Array, w: jax.Array,
             bias: Optional[jax.Array] = None,
             scale: Optional[jax.Array] = None,
             *, epilogue: str = "none", shift: int = 0,
             use_pallas: bool = False, interpret: Optional[bool] = None,
             bm: int = 128, bn: int = 128, bk: int = 128) -> jax.Array:
    """int8 x int8 -> int32 GEMM with fused VTA epilogue.

    a: (M, K) int8;  w: (K, N) int8;  bias: (N,) int32;  scale: (N,) f32.
    use_pallas=False runs the jnp oracle (identical math) — used by the
    dry-run so cost_analysis sees real FLOPs; tests exercise both paths.
    interpret=None follows the platform (native on TPU, interpreter
    elsewhere; see ``kernels._platform``).
    """
    if not use_pallas:
        return vta_gemm_ref(a, w, bias, scale, epilogue=epilogue, shift=shift)
    M, K = a.shape
    _, N = w.shape
    ap = _pad_to(_pad_to(a, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w, 0, bk), 1, bn)
    bp = _pad_to(bias, 0, bn) if bias is not None else None
    sp = _pad_to(scale, 0, bn) if scale is not None else None
    out = vta_gemm_pallas(ap, wp, bp, sp, epilogue=epilogue, shift=shift,
                          bm=bm, bn=bn, bk=bk,
                          interpret=interpret)
    return out[:M, :N]


def quantized_linear(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                     x_scale: Optional[jax.Array] = None,
                     *, use_pallas: bool = False,
                     interpret: Optional[bool] = None) -> jax.Array:
    """LM serving path: y(f32) = (x_q @ w_q) * (sx * sw[n]).

    x: float activations -> dynamically quantized to int8 per-tensor;
    w_q: (K, N) int8 with per-channel scales. This is the paper's PTQ
    deployment scheme lifted to the LM stack.
    """
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    if x_scale is None:
        amax = jnp.maximum(jnp.max(jnp.abs(x2)), 1e-6)
        x_scale = (amax / 127.0).astype(jnp.float32)
    x_q = jnp.clip(jnp.round(x2 / x_scale), -128, 127).astype(jnp.int8)
    scale = (w_scale.astype(jnp.float32) * x_scale).astype(jnp.float32)
    y = vta_gemm(x_q, w_q, scale=scale, epilogue="dequant",
                 use_pallas=use_pallas, interpret=interpret)
    return y.reshape(*orig_shape[:-1], w_q.shape[1]).astype(x.dtype)
