"""Causal GQA flash attention (prefill), Pallas TPU.

VTA's decoupled access-execute pattern applied to attention: the KV
stream is consumed block-by-block from HBM while the MXU computes the
running-softmax update for the previous block (grid pipelining double-
buffers the DMA exactly like VTA's load/compute FIFO overlap).  Scratch
(m, l, acc) lives in VMEM — the explicit "register file" of the kernel.

Grid: (batch*q_heads, q_blocks, kv_blocks), kv innermost ("arbitrary"),
rest parallel.  GQA: the kv BlockSpec index_map folds the q-head index
onto its kv head (h // group), so no host-side KV replication is needed.
Causality: kv blocks strictly above the diagonal are skipped via pl.when
(no wasted MXU work); the diagonal block is masked.

VMEM working set per step (bq=bk=256, D=128, f32):
  q/acc (bq, D)*2 + k/v (bk, D)*2 + scores (bq, bk) ~= 0.8 MiB « VMEM.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, scale: float, causal: bool, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip kv blocks entirely above the causal diagonal
        pl.when(ik * bk <= iq * bq + bq - 1)(body)
    else:
        body()

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("group", "causal", "bq", "bk", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           group: int, causal: bool = True,
                           bq: int = 256, bk: int = 256,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q: (B*HQ, S, D);  k/v: (B*KH, S, D);  group = HQ // KH.

    The kv index_map sends q head h to kv head h // group — GQA without
    materializing replicated KV.
    """
    BH, S, D = q.shape
    _, Sk, _ = k.shape
    assert BH % group == 0
    bq = min(bq, S)
    bk = min(bk, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, Sk, bq, bk)
    nk = Sk // bk
    scale = 1.0 / (D ** 0.5)

    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, nk=nk),
        grid=(BH, S // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h // group, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((bq, D), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
