"""VTA tensor ALU, TPU-native (Pallas).

The FPGA tensor ALU performs element-wise MIN/MAX/ADD/SHR/MUL over
register-file tensors (tensor-tensor or tensor-immediate, Fig. 8) at an
initiation interval >= 2 because the register file has one read port.  On
TPU the VPU performs these over (8,128) vregs; the kernel streams int32
blocks through VMEM.  Fused chains (e.g. shift->max->min = requantize+clip)
run in one pass — the resource-balance trade §2.5 discusses.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._platform import resolve_interpret

ALU_OPS = ("min", "max", "add", "shr", "mul")


def _apply(op: str, x: jax.Array, y: jax.Array) -> jax.Array:
    if op == "min":
        return jnp.minimum(x, y)
    if op == "max":
        return jnp.maximum(x, y)
    if op == "add":
        return x + y
    if op == "mul":
        return x * y
    if op == "shr":
        # VTA semantics: negative shift = shift left
        return jnp.where(y >= 0,
                         jax.lax.shift_right_arithmetic(x, y),
                         jax.lax.shift_left(x, -y))
    raise ValueError(op)


def _alu_kernel(dst_ref, src_ref, o_ref, *, chain: Tuple[Tuple[str, Optional[int]], ...]):
    x = dst_ref[...]
    src = src_ref[...] if src_ref is not None else None
    for op, imm in chain:
        y = jnp.full_like(x, imm) if imm is not None else src
        x = _apply(op, x, y)
    o_ref[...] = x


@functools.partial(jax.jit,
                   static_argnames=("chain", "bm", "bn", "interpret"))
def tensor_alu_pallas(dst: jax.Array, src: Optional[jax.Array] = None,
                      *, chain: Tuple[Tuple[str, Optional[int]], ...],
                      bm: int = 256, bn: int = 512,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Apply a chain of VTA ALU ops to an int32 tensor.

    chain: tuple of (op, imm) — imm=None means tensor-tensor with `src`.
    dst/src: (M, N) int32, blocked (bm, bn) over both axes; M must divide
    into bm-row blocks and N into bn-column blocks (bn a multiple of the
    128-lane width).  At the default (256, 512) one block is 512 KiB, so
    dst, src and out double-buffered take 3 MiB of the 16 MiB scoped VMEM
    whatever the tensor's size.
    """
    M, N = dst.shape
    bm = min(bm, M)
    bn = min(bn, N)
    assert M % bm == 0 and N % bn == 0, ((M, N), (bm, bn))
    has_src = any(imm is None for _, imm in chain)
    block = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    in_specs = [block]
    args = [dst]
    if has_src:
        assert src is not None
        in_specs.append(block)
        args.append(src)

    def kernel(*refs):
        if has_src:
            d_ref, s_ref, o_ref = refs
        else:
            (d_ref, o_ref), s_ref = refs, None
        _alu_kernel(d_ref, s_ref, o_ref, chain=chain)

    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn),
        in_specs=in_specs,
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
    )(*args)
