"""Sub-byte weight GEMM by bit-plane decomposition (Pallas).

The T-MAC view of a sub-byte GEMM: decompose each b-bit two's-complement
weight into its bit planes,

    w = sum_{t<b-1} 2^t * bit_t  -  2^(b-1) * bit_{b-1},

so the GEMM becomes b GEMMs against 0/1 matrices, recombined with the
plane coefficients:

    acc[m, n] = sum_t coef_t * sum_k a[m, k] * bit_t[k, n].

T-MAC evaluates the inner sum by looking up precomputed subset sums of
the activations (a table indexed by g weight bits at a time).  That
lookup is a 3-D dynamic gather, which Mosaic does not lower ("Only 2D
gather is supported"); the same sum is a contraction of the activations
against the 0/1 plane, which the MXU runs natively as int8 x int8 ->
int32.  The kernel therefore issues one MXU pass per bit plane over the
weight column block it holds in VMEM.

All arithmetic is exact int32, so the result is BIT-IDENTICAL to the
dense int8 GEMM over the sign-extended weights — the property the
cross-backend fuzzer locks in.  The fused requant epilogue reproduces
``vta_gemm``'s exactly (truncating arithmetic shift, clip, int8).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._platform import resolve_interpret


def _lut_kernel(a_ref, w_ref, o_ref, *, bits: int, epilogue: str,
                shift: int):
    a = a_ref[...]                            # (M, K) int8
    w = w_ref[...].astype(jnp.int32)          # (K, bn) sign-extended b-bit
    acc = None
    for t in range(bits):
        plane = ((w >> t) & 1).astype(jnp.int8)
        part = jax.lax.dot_general(
            a, plane, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        coef = -(1 << t) if t == bits - 1 else (1 << t)   # MSB = sign plane
        part = part * jnp.int32(coef)
        acc = part if acc is None else acc + part

    if epilogue == "none":
        o_ref[...] = acc
    elif epilogue == "requant":
        q = jax.lax.shift_right_arithmetic(acc, jnp.int32(shift))
        o_ref[...] = jnp.clip(q, -128, 127).astype(jnp.int8)
    else:
        raise ValueError(epilogue)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "epilogue", "shift", "bn", "interpret"))
def lut_gemm_pallas(a: jax.Array, w: jax.Array, *, bits: int,
                    epilogue: str = "none", shift: int = 0,
                    bn: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """C[M,N] = epilogue(A[M,K](int8) @ W[K,N](int{bits})) by bit planes.

    Same operand/epilogue contract as ``vta_gemm_pallas`` (so the backend
    can swap it in per shape), minus bias/dequant which the decode path
    never fuses.  `w` values must lie in the b-bit two's-complement range
    (they are the sign-extended int8 the WGT SRAM holds); N must be a
    multiple of `bn`.  `interpret` resolves from the platform when None.
    """
    if bits not in (1, 2, 4):
        raise ValueError(f"lut_gemm: bits must be 1, 2 or 4, got {bits}")
    M, K = a.shape
    K2, N = w.shape
    assert K == K2, (a.shape, w.shape)
    assert N % bn == 0, f"pad N to a multiple of bn: {N} vs {bn}"
    out_dtype = {"none": jnp.int32, "requant": jnp.int8}[epilogue]

    return pl.pallas_call(
        functools.partial(_lut_kernel, bits=bits, epilogue=epilogue,
                          shift=shift),
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((M, K), lambda j: (0, 0)),   # activations (small M)
            pl.BlockSpec((K, bn), lambda j: (0, j)),  # weight column block
        ],
        out_specs=pl.BlockSpec((M, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
    )(a, w)
