"""Public op: GLA chunk scan over (B, S, H, ...) tensors."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import gla_chunk_pallas
from .ref import gla_chunk_ref


def gla_chunk(q: jax.Array, k: jax.Array, v: jax.Array, la: jax.Array,
              h0: Optional[jax.Array] = None, *, chunk: int = 64,
              use_pallas: bool = False,
              interpret: Optional[bool] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """q,k: (B, S, H, N); v: (B, S, H, P); la: (B, S, H) log-decay;
    h0: (B, H, N, P) or None.  Returns (y (B,S,H,P), h (B,H,N,P))."""
    B, S, H, N = q.shape
    P_ = v.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    def to_bh(x, feat):
        return (x.transpose(0, 2, 1, 3)
                .reshape(B * H, nc, Q, feat))

    qb, kb = to_bh(q, N), to_bh(k, N)
    vb = to_bh(v, P_)
    lab = la.transpose(0, 2, 1).reshape(B * H, nc, Q)
    h0b = (jnp.zeros((B * H, N, P_), jnp.float32) if h0 is None
           else h0.reshape(B * H, N, P_).astype(jnp.float32))
    fn = gla_chunk_pallas if use_pallas else gla_chunk_ref
    kw = {"interpret": interpret} if use_pallas else {}
    yb, hb = fn(qb, kb, vb, lab, h0b, **kw)
    y = yb.reshape(B, H, S, P_).transpose(0, 2, 1, 3)
    return y, hb.reshape(B, H, N, P_)
