"""Plain reference for the ``joyai_moe`` model: numpy only.

It imports nothing of the system under test and computes one MoE layer's
share token by token and expert by expert, from the configuration's
``fixed_point`` rules: every GEMM is an exact integer product (float64
sums of int8 products over at most 2048 terms are exact) followed by an
arithmetic shift right and a clip to int8; the router's int8 logits pick
each token's experts through the sigmoid score table plus the correction
bias, ties to the lower expert index; the gates are the selected unbiased
scores normalized and scaled in integer arithmetic; each held expert's
SwiGLU runs on the tokens that picked it, the shared experts on all; the
gate-weighted sum, in int64, is shifted and clipped to int8.  Experts not
held add nothing.

``wgt_bits`` below 8 is the control: every weight is first rounded to a
``wgt_bits``-bit grid over the int8 range.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

LEVELS = np.arange(-128, 128, dtype=np.float64)


def lower_precision(w: np.ndarray, wgt_bits: int) -> np.ndarray:
    if wgt_bits >= 8:
        return w
    step = 1 << (8 - wgt_bits)
    lo, hi = -(1 << (wgt_bits - 1)), (1 << (wgt_bits - 1)) - 1
    return np.clip(np.round(w.astype(np.float64) / step), lo, hi) * step


def gemm_q(a: np.ndarray, w: np.ndarray, shift: int) -> np.ndarray:
    """clip((a @ w^T) >> shift) as int64 values in [-128, 127]."""
    acc = (a.astype(np.float64) @ w.astype(np.float64).T).astype(np.int64)
    return np.clip(acc >> shift, -128, 127)


def expert(fp: Dict, x: np.ndarray, gate_up: np.ndarray,
           down: np.ndarray) -> np.ndarray:
    """One SwiGLU expert on int8 tokens x: int64 (n, d) in [-128, 127]."""
    f = gate_up.shape[0] // 2
    gu = gemm_q(x, gate_up, fp["gate_up_shift"])
    z = LEVELS / 2.0 ** fp["act_frac"]
    silu = np.clip(np.rint(z / (1.0 + np.exp(-z)) * 2.0 ** fp["act_frac"]),
                   -128, 127).astype(np.int64)
    h = np.clip((silu[gu[:, :f] + 128] * gu[:, f:]) >> fp["act_shift"],
                -128, 127)
    return gemm_q(h, down, fp["down_shift"])


def routing(cfg: Dict, logits: np.ndarray, bias: np.ndarray
            ) -> Tuple[np.ndarray, np.ndarray]:
    """Per token: its top-k experts and their integer gates."""
    fp = cfg["fixed_point"]
    k = cfg["num_experts_per_tok"]
    table = np.rint(2.0 ** fp["score_frac"]
                    / (1.0 + np.exp(-LEVELS / 2.0 ** fp["logit_frac"]))
                    ).astype(np.int64)
    G = int(np.rint(cfg["routed_scaling_factor"] * 2 ** fp["gate_frac"]))
    T, E = logits.shape
    index = np.arange(E)
    experts = np.empty((T, k), np.int64)
    gates = np.empty((T, k), np.int64)
    for t in range(T):
        score = table[logits[t].astype(np.int64) + 128]
        key = score + bias.astype(np.int64)
        # descending key, then ascending expert index
        pick = np.lexsort((index, -key))[:k]
        s = score[pick]
        tot = max(int(s.sum()), 1)               # norm_topk_prob
        experts[t], gates[t] = pick, (s * G + tot // 2) // tot
    return experts, gates


def layer(cfg: Dict, held: List[int], w: Dict, x: np.ndarray,
          wgt_bits: int = 8) -> Tuple[np.ndarray, np.ndarray]:
    """(int8 (T, d) output of one layer's share, rows routed to each held
    expert)."""
    fp = cfg["fixed_point"]
    router = lower_precision(w["router"], wgt_bits)
    gate_up = lower_precision(w["gate_up"], wgt_bits)
    down = lower_precision(w["down"], wgt_bits)
    logits = gemm_q(x, router, fp["router_shift"])
    experts, gates = routing(cfg, logits, w["bias"])
    acc = np.zeros(x.shape, np.int64)
    load = np.zeros(len(held), np.int64)
    for slot, e in enumerate(held):
        tok, j = np.nonzero(experts == e)
        load[slot] = tok.size
        if tok.size:
            acc[tok] += gates[tok, j][:, None] * expert(
                fp, x[tok], gate_up[slot], down[slot])
    for slot in range(len(held), len(held) + cfg["n_shared_experts"]):
        acc += expert(fp, x, gate_up[slot], down[slot]) << fp["gate_frac"]
    y = np.clip(acc >> fp["out_shift"], -128, 127).astype(np.int8)
    return y, load
