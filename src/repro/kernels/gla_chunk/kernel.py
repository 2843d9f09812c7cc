"""Chunked gated-linear-attention scan (Mamba2 SSD / mLSTM), Pallas TPU.

The cross-chunk recurrence h_c = d_c * h_{c-1} + state_c is inherently
sequential — exactly the situation VTA's decoupled access-execute targets:
while the MXU computes chunk c (intra-chunk quadratic + state update),
the grid pipeline DMAs chunk c+1's q/k/v blocks from HBM.  The recurrent
state h lives in VMEM scratch across grid steps (the "register file"),
so the sequential dependency never round-trips HBM.

Grid: (B*H, n_chunks); chunk dim is "arbitrary" (ordered), batch*heads
parallel.  Per-step working set (Q=64, N=64, P=64, f32):
q/k (Q,N) + v/y (Q,P) + scores (Q,Q) + h (N,P) ~= 80 KiB « VMEM.

Math per chunk (L = within-chunk cumsum of log-decay):
    y = (q·kᵀ ⊙ exp(L_i − L_j) ⊙ causal) v  +  (q ⊙ exp(L)) h
    h = exp(L_tot) h + (k ⊙ exp(L_tot − L))ᵀ v
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._platform import resolve_interpret


def _gla_kernel(q_ref, k_ref, v_ref, la_ref, h0_ref, y_ref, hout_ref,
                h_ref, *, nc: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)       # (Q, N)
    k = k_ref[0, 0].astype(jnp.float32)       # (Q, N)
    v = v_ref[0, 0].astype(jnp.float32)       # (Q, P)
    la = la_ref[0, 0].astype(jnp.float32)     # (Q,)
    L = jnp.cumsum(la)                        # (Q,)
    Ltot = L[-1]

    # intra-chunk: causal decay-weighted attention
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    Q = s.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    decay = jnp.where(ii >= jj, L[:, None] - L[None, :], -jnp.inf)
    y = jax.lax.dot_general(s * jnp.exp(decay), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk: contribution of the carried state
    h = h_ref[...]
    y = y + jax.lax.dot_general(q * jnp.exp(L)[:, None], h,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update
    ks = k * jnp.exp(Ltot - L)[:, None]
    h_ref[...] = h * jnp.exp(Ltot) + jax.lax.dot_general(
        ks, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(c == nc - 1)
    def _finish():
        hout_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gla_chunk_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                     la: jax.Array, h0: jax.Array, *,
                     interpret: Optional[bool] = None):
    """q,k: (BH, nc, Q, N); v: (BH, nc, Q, P); la: (BH, nc, Q);
    h0: (BH, N, P) f32.  Returns (y: (BH, nc, Q, P), h: (BH, N, P))."""
    BH, nc, Q, N = q.shape
    P_ = v.shape[-1]
    return pl.pallas_call(
        functools.partial(_gla_kernel, nc=nc),
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, P_), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, P_), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P_), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, N, P_), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, Q, P_), q.dtype),
            jax.ShapeDtypeStruct((BH, N, P_), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v, la, h0)
