"""Public op: (B, S, H, D)-layout GQA attention with pallas/ref dispatch."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.meshctx import constrain

from .kernel import flash_attention_pallas
from .ref import attention_ref, attention_ref_chunked

# the merged (batch*heads) dim shards over the WHOLE mesh — attention is
# embarrassingly parallel across it; without this constraint GSPMD keeps
# only one mesh axis and replicates the other (16x redundant compute)
_BH_AXES = ("pod", "data", "model")

# above this many score elements per head, the materialized oracle would
# dominate memory — switch to the lax.scan flash formulation
_CHUNKED_THRESHOLD = 2048 * 2048


def _to_heads(x: jax.Array) -> jax.Array:
    """(B, S, H, D) -> (B*H, S, D)"""
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from_heads(x: jax.Array, B: int) -> jax.Array:
    BH, S, D = x.shape
    H = BH // B
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, use_pallas: bool = False,
                    interpret: Optional[bool] = None, bq: int = 256,
                    bk: int = 256) -> jax.Array:
    """q: (B, S, HQ, D); k/v: (B, S, KH, D). Returns (B, S, HQ, D)."""
    B, S, HQ, D = q.shape
    KH = k.shape[2]
    group = HQ // KH
    if use_pallas:
        qh, kh, vh = _to_heads(q), _to_heads(k), _to_heads(v)
        out = flash_attention_pallas(qh, kh, vh, group=group, causal=causal,
                                     bq=bq, bk=bk, interpret=interpret)
        return _from_heads(out, B)
    if S * k.shape[1] > _CHUNKED_THRESHOLD:
        # sequence parallelism: q rows are independent — shard the q seq
        # dim over "model" (uniform across head counts), batch over data
        data = ("pod", "data")
        q = constrain(q, data, "model", None, None)
        k = constrain(k, data, "model", None, None)
        v = constrain(v, data, "model", None, None)
        out = attention_ref_chunked(q, k, v, group=group, causal=causal)
        return constrain(out, data, "model", None, None)
    qh, kh, vh = _to_heads(q), _to_heads(k), _to_heads(v)
    out = attention_ref(qh, kh, vh, group=group, causal=causal)
    return _from_heads(out, B)
