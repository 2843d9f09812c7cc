"""VTA GEMM core, TPU-native (Pallas).

The FPGA design's (BATCH x BLOCK_IN x BLOCK_OUT) single-cycle intrinsic
becomes the MXU's 128x128 systolic matmul; the data-specialized SRAMs
become per-operand VMEM blocks with explicit BlockSpecs; decoupled
access-execute becomes Mosaic's grid software pipeline (HBM->VMEM DMA for
block k+1 overlaps the MXU pass over block k — the same load/compute
overlap VTA achieves with dependence-token FIFOs); and the tensor-ALU
epilogue (bias / shift-requantize / clip, §2.5) is fused after the last
reduction step so the register file is written once.

Semantics (faithful to the VTA datapath):
    acc(int32) = sum_k  A(int8) @ W(int8)
    epilogue:
      "none":    out = acc                                  (int32)
      "requant": out = clip((acc + bias) >> shift) as int8  (truncating SHR)
      "dequant": out = (acc + bias) * scale as float32      (LM serving path)

The MXU is fed int8 x int8 with an int32 result type: Mosaic has no
int32 x int32 matmul, so the operands are never widened before the dot.

Block shapes default to (128, 128, 128): MXU-aligned (int8 min tile is
(32,128); 128x128 keeps both matmul operands and the int32 accumulator at
hardware-native tiling).  VMEM working set per grid step:
    bm*bk (A, int8) + bk*bn (W, int8) + bm*bn*4 (acc) + out block
  = 16 KiB + 16 KiB + 64 KiB + <=64 KiB  «  ~16 MiB VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._platform import resolve_interpret


def _gemm_kernel(a_ref, w_ref, bias_ref, scale_ref, o_ref, acc_ref, *,
                 epilogue: str, shift: int, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...]
        if bias_ref is not None:
            acc = acc + bias_ref[...].astype(jnp.int32)
        if epilogue == "none":
            o_ref[...] = acc
        elif epilogue == "requant":
            # VTA SHR is a truncating arithmetic shift; clip = tensor-ALU
            # MIN/MAX pair; the OUT store narrows to int8.
            q = jax.lax.shift_right_arithmetic(acc, jnp.int32(shift))
            o_ref[...] = jnp.clip(q, -128, 127).astype(jnp.int8)
        elif epilogue == "dequant":
            o_ref[...] = acc.astype(jnp.float32) * scale_ref[...]
        else:
            raise ValueError(epilogue)


@functools.partial(
    jax.jit,
    static_argnames=("epilogue", "shift", "bm", "bn", "bk", "interpret"))
def vta_gemm_pallas(a: jax.Array, w: jax.Array,
                    bias: Optional[jax.Array] = None,
                    scale: Optional[jax.Array] = None,
                    *, epilogue: str = "none", shift: int = 0,
                    bm: int = 128, bn: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """C[M,N] = epilogue(A[M,K](int8) @ W[K,N](int8) + bias).

    bias: (N,) int32, scale: (N,) float32 (per-output-channel, like VTA's
    per-filter requant constants).  `interpret` resolves from the
    platform when None (see ``kernels._platform``).
    """
    M, K = a.shape
    K2, N = w.shape
    assert K == K2, (a.shape, w.shape)
    if a.dtype != jnp.int8 or w.dtype != jnp.int8:
        raise TypeError(f"vta_gemm takes int8 operands (the MXU's int8 "
                        f"path), got {a.dtype} x {w.dtype}")
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"pad shapes to block multiples: {(M, N, K)} vs {(bm, bn, bk)}"
    nk = K // bk
    out_dtype = {"none": jnp.int32, "requant": jnp.int8,
                 "dequant": jnp.float32}[epilogue]

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # A tile (inp buffer)
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # W tile (wgt buffer)
    ]
    args = [a, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(bias.reshape(1, N))
    if epilogue == "dequant":
        assert scale is not None
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(scale.reshape(1, N))

    def kernel(*refs):
        a_ref, w_ref = refs[0], refs[1]
        idx = 2
        b_ref = None
        s_ref = None
        if bias is not None:
            b_ref = refs[idx]; idx += 1
        if epilogue == "dequant":
            s_ref = refs[idx]; idx += 1
        o_ref, acc_ref = refs[idx], refs[idx + 1]
        _gemm_kernel(a_ref, w_ref, b_ref, s_ref, o_ref, acc_ref,
                     epilogue=epilogue, shift=shift, nk=nk)

    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],  # register file
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(*args)
