"""Public op: batched GQA decode step over a (possibly padded) KV cache."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.meshctx import constrain

from .kernel import decode_attention_pallas
from .ref import decode_attention_ref, decode_attention_ref_4d


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array, *, use_pallas: bool = False,
                     interpret: Optional[bool] = None,
                     bk: int = 512) -> jax.Array:
    """q: (B, 1, HQ, D); caches: (B, S, KH, D); kv_len: scalar int32.
    Returns (B, 1, HQ, D)."""
    B, _, HQ, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = HQ // KH
    if use_pallas:
        qh = q.reshape(B, HQ, D).reshape(B, KH, G, D).reshape(B * KH, G, D)
        kh = k_cache.transpose(0, 2, 1, 3).reshape(B * KH, S, D)
        vh = v_cache.transpose(0, 2, 1, 3).reshape(B * KH, S, D)
        out = decode_attention_pallas(qh, kh, vh, jnp.asarray(kv_len),
                                      bk=bk, interpret=interpret)
        return out.reshape(B, KH, G, D).reshape(B, 1, HQ, D)
    # cache-native path: no transpose of the cache; works with a
    # sequence-sharded cache (GSPMD flash-decoding)
    return decode_attention_ref_4d(q, k_cache, v_cache, jnp.asarray(kv_len))
