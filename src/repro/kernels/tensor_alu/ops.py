"""Public op wrapper for the VTA tensor ALU."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import tensor_alu_pallas
from .ref import tensor_alu_ref

_LANES = 128  # VPU lane width: last dim of a native tile
_MAX_COL_BLOCK = 512  # (256, 512) int32 blocks: 3 MiB of VMEM double-buffered


def _col_block(n: int) -> int:
    """Widest lane-multiple column block, at most _MAX_COL_BLOCK, that
    divides the lane-aligned width `n` (no padding beyond the lane
    round-up)."""
    lanes = n // _LANES
    k = max(d for d in range(1, _MAX_COL_BLOCK // _LANES + 1)
            if lanes % d == 0)
    return k * _LANES


def tensor_alu(dst: jax.Array, src: Optional[jax.Array] = None,
               *, chain: Tuple[Tuple[str, Optional[int]], ...],
               use_pallas: bool = False,
               interpret: Optional[bool] = None,
               bm: int = 256) -> jax.Array:
    if not use_pallas:
        return tensor_alu_ref(dst, src, chain=chain)
    # The kernel wants rows in bm-sized blocks and lane-aligned columns;
    # callers (e.g. the execution backend's tile epilogues) hand it
    # arbitrary tile shapes, so pad here and slice the result back.
    M, N = dst.shape
    bm_eff = min(bm, M)
    pad_m = (-M) % bm_eff
    pad_n = (-N) % _LANES
    if pad_m or pad_n:
        widths = ((0, pad_m), (0, pad_n))
        dst = jnp.pad(dst, widths)
        if src is not None:
            src = jnp.pad(src, widths)
    out = tensor_alu_pallas(dst, src, chain=chain, bm=bm_eff,
                            bn=_col_block(N + pad_n),
                            interpret=interpret)
    if pad_m or pad_n:
        out = out[:M, :N]
    return out


def requantize(acc: jax.Array, shift: int, lo: int = -128,
               hi: int = 127, **kw) -> jax.Array:
    """The canonical VTA epilogue: SHR then clip (MIN/MAX pair)."""
    return tensor_alu(acc, chain=(("shr", shift), ("max", lo), ("min", hi)),
                      **kw)
