"""Single-step decode attention over a long KV cache (flash-decoding).

The decode cells (decode_32k / long_500k) are KV-bandwidth-bound: one new
query token attends over S cached keys.  The kernel streams the KV cache
through VMEM in seq blocks (grid innermost dim) with a running softmax —
arithmetic intensity ~2 flops/byte, so the roofline is the HBM stream rate
and the job of the kernel is purely to keep the DMA saturated (VTA's
latency-hiding argument in its purest form).

All G q-heads of one kv head are processed together so the KV block is
read once per group rather than once per head (G-fold HBM traffic saving
— same motivation as VTA's weight-buffer reuse).

Grid: (B*KH, S//bk).  q block: (1, G, D); kv block: (1, bk, D);
scratch m/l: (G, 1), acc: (G, D).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._platform import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, bk: int, scale: float, nk: int):
    ik = pl.program_id(1)
    kv_len = len_ref[0]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ik * bk < kv_len)  # skip blocks beyond the valid cache length
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (G, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bk)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            kv_len: jax.Array, *, bk: int = 512,
                            interpret: Optional[bool] = None) -> jax.Array:
    """q: (B*KH, G, D) one new token per sequence, grouped per kv head;
    k/v: (B*KH, S, D) cache (padded to S); kv_len: (1,) int32 valid length.
    """
    BH, G, D = q.shape
    _, S, _ = k.shape
    bk = min(bk, S)
    assert S % bk == 0
    nk = S // bk
    scale = 1.0 / (D ** 0.5)

    return pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk, scale=scale, nk=nk),
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_len scalar
            pl.BlockSpec((1, G, D), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda h, j: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(kv_len.astype(jnp.int32).reshape(1), q, k, v)
