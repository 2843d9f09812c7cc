"""A routed mixture-of-experts layer on the compiled VTA path.

The DeepSeek-V3 block (sigmoid router with the ``noaux_tc`` correction
bias, top-k of the routed experts, weights normalized over the k and
scaled, SwiGLU experts, shared experts added with weight one), as one
:class:`~repro.core.program.Program` that holds a given set of the routed
experts: it routes every token over all of them and computes only its own
experts' part, which is the chip's share under expert parallelism.  One
call is one step of ``tokens`` int8 rows of width ``hidden``:

  1. router GEMM on the accelerator: ``logits = clip((x @ Wr^T) >>
     router_shift)``, int8 (T, n_experts);
  2. host step ``route`` (span ``vta.moe.route``): scores, top-k, gates,
     and the pairs that land on held experts (:func:`route`); then host
     step ``dispatch`` (same span): each held expert's rows gathered at
     the head of its slot (:func:`dispatch`);
  3. ``Program.expert_matmul`` for gate and up together over the rows
     routed to each held expert, rounded up to the template's batch; an
     expert with no rows launches nothing.  The JIT emits each (expert,
     padded rows) stream on first use and replays it afterwards;
  4. host step ``act`` (span ``vta.moe.act``): SiLU by table times up
     (:func:`act`);
  5. ``expert_matmul`` for down;
  6. host step ``combine`` (span ``vta.moe.combine``): the gate-weighted
     sum of the routed outputs plus the shared ones (:func:`combine`).

The shared experts are the last slots of both expert segments and run
every token.  No token is dropped and there is no capacity factor.

Fixed-point rules (all shifts arithmetic, i.e. floor; every clip is to
[-128, 127]; "rint" rounds half to even):

  * GEMM epilogues: ``clip(acc >> shift)`` with ``router_shift``,
    ``gate_up_shift`` (gate and up share one) and ``down_shift``.
  * score: ``score_q[l] = rint(2**score_frac / (1 + exp(-l /
    2**logit_frac)))`` for each int8 logit l, from a 256-entry table.
  * selection: the top ``top_k`` experts by ``score_q + bias_q`` (bias in
    score units, int32), ties to the lowest expert index.
  * gates (``norm_topk_prob``): ``gate_q = (score_q * G + sum // 2) //
    sum`` over the k selected unbiased scores, ``G = rint(routed_scaling
    * 2**gate_frac)``.
  * SiLU: ``silu_q[g] = clip(rint(silu(g / 2**act_frac) * 2**act_frac))``
    from a 256-entry table; ``h = clip((silu_q[g] * u) >> act_shift)``.
  * combine: ``acc = sum(gate_q * y_e) + 2**gate_frac * sum(y_shared)``
    in int64 (exact, so the summation order does not matter; it fits in
    int32), then ``y = clip(acc >> out_shift)``.

:func:`reference_int` computes the same rules expert by expert, with no
slots, buffers or streams: the compiled layer matches it bit for bit.
:func:`reference_f32` is the published block in float32 ``jax.numpy``;
the integer layer departs from it by the int8 requantizations, the two
tables and the rounded gates (:data:`F32_TOLERANCE`).
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Sequence, Tuple

import numpy as np

from repro.core import hwspec as _hwspec
from repro.core.program import Program
from repro.core.scheduler import Epilogue


@dataclass(frozen=True)
class MoEConfig:
    """Widths of the block and the fixed-point rules of the module
    docstring.  The defaults are JoyAI-LLM-Flash's MoE layer
    (huggingface.co/jdopensource/JoyAI-LLM-Flash, config.json) at a
    256-token step."""
    hidden: int = 2048
    expert_width: int = 768
    n_experts: int = 256
    top_k: int = 8
    n_shared: int = 1
    routed_scaling: float = 2.5
    tokens: int = 256
    router_shift: int = 13
    logit_frac: int = 4
    score_frac: int = 15
    gate_frac: int = 12
    gate_up_shift: int = 13
    act_frac: int = 4
    act_shift: int = 5
    down_shift: int = 10
    out_shift: int = 12

    @property
    def gate_scale(self) -> int:
        return int(np.rint(self.routed_scaling * 2 ** self.gate_frac))

    def meta_len(self, slots: int) -> int:
        """Length of the routing table :func:`route` writes."""
        return slots + 3 * self.tokens * self.top_k


@dataclass(frozen=True)
class MoEWeights:
    """One layer's int8 weights: the router over every routed expert, the
    ``noaux_tc`` correction bias (int32, score units), and per slot (the
    held routed experts, then the shared ones) gate and up stacked, (2f,
    d), and down, (d, f)."""
    router: np.ndarray           # (n_experts, d) int8
    bias: np.ndarray             # (n_experts,) int32
    gate_up: np.ndarray          # (slots, 2f, d) int8
    down: np.ndarray             # (slots, d, f) int8


def random_weights(cfg: MoEConfig, held: Sequence[int],
                   rng: np.random.Generator) -> MoEWeights:
    """Uniform int8 weights for the held experts and the shared ones, and
    a correction bias uniform over +-128 score units for every routed
    expert; experts not held get no weights, as on the chip that holds
    this share."""
    d, f, S = cfg.hidden, cfg.expert_width, len(held) + cfg.n_shared
    i8 = dict(dtype=np.int8, endpoint=True)
    return MoEWeights(
        router=rng.integers(-128, 127, (cfg.n_experts, d), **i8),
        bias=rng.integers(-128, 128, cfg.n_experts,
                          endpoint=True).astype(np.int32),
        gate_up=rng.integers(-128, 127, (S, 2 * f, d), **i8),
        down=rng.integers(-128, 127, (S, d, f), **i8))


# ----------------------------------------------------------------------
# the fixed-point rules
# ----------------------------------------------------------------------
_INT8 = np.arange(-128, 128, dtype=np.float64)


def score_table(cfg: MoEConfig) -> np.ndarray:
    """score_q for every int8 logit, indexed by logit + 128."""
    return np.rint(2.0 ** cfg.score_frac
                   / (1.0 + np.exp(-_INT8 / 2.0 ** cfg.logit_frac))
                   ).astype(np.int64)


def silu_table(cfg: MoEConfig) -> np.ndarray:
    """silu_q for every int8 gate value, indexed by value + 128."""
    z = _INT8 / 2.0 ** cfg.act_frac
    return np.clip(np.rint(z / (1.0 + np.exp(-z)) * 2.0 ** cfg.act_frac),
                   -128, 127).astype(np.int64)


def _requant(acc: np.ndarray, shift: int) -> np.ndarray:
    return np.clip(acc >> shift, -128, 127).astype(np.int8)


def select(cfg: MoEConfig, logits: np.ndarray, bias: np.ndarray
           ) -> Tuple[np.ndarray, np.ndarray]:
    """(experts (T, k), gate_q (T, k)) of int8 logits (T, n_experts)."""
    score = score_table(cfg)[logits.astype(np.int64) + 128]
    order = np.argsort(-(score + bias.astype(np.int64)), axis=1,
                       kind="stable")
    top = order[:, :cfg.top_k]
    s = np.take_along_axis(score, top, axis=1)
    tot = np.maximum(s.sum(axis=1, keepdims=True), 1)
    return top, (s * cfg.gate_scale + tot // 2) // tot


def route(cfg: MoEConfig, held: Sequence[int], logits: np.ndarray,
          bias: np.ndarray) -> np.ndarray:
    """The routing table of one call, int32: per slot its live rows
    (shared slots: every token), then per (token, k) pair in token order
    its slot (-1 where the expert is not held), its row in the slot, and
    its gate_q."""
    T, K = cfg.tokens, cfg.top_k
    S = len(held) + cfg.n_shared
    top, gate = select(cfg, logits, bias)
    slot_of = np.full(cfg.n_experts, -1, np.int64)
    slot_of[np.asarray(held, np.int64)] = np.arange(len(held))
    slot = slot_of[top].ravel()
    counts = np.bincount(slot[slot >= 0], minlength=S)
    counts[len(held):] = T
    row = np.full(T * K, -1, np.int64)
    mine = np.flatnonzero(slot >= 0)
    by_slot = mine[np.argsort(slot[mine], kind="stable")]
    starts = np.concatenate([[0], np.cumsum(counts[:len(held)])])
    ranks = np.arange(by_slot.size) - starts[slot[by_slot]]
    row[by_slot] = ranks
    return np.concatenate([counts, slot, row, gate.ravel()]).astype(np.int32)


def unpack_route(cfg: MoEConfig, slots: int, meta: np.ndarray):
    """(counts, token, slot, row, gate) of the held pairs of a routing
    table."""
    P = cfg.tokens * cfg.top_k
    meta = meta.astype(np.int64)
    counts = meta[:slots]
    slot, row, gate = (meta[slots + i * P:slots + (i + 1) * P]
                       for i in range(3))
    mine = np.flatnonzero(slot >= 0)
    return counts, mine // cfg.top_k, slot[mine], row[mine], gate[mine]


def dispatch(cfg: MoEConfig, slots: int, x: np.ndarray,
             meta: np.ndarray) -> np.ndarray:
    """Each held expert's tokens at the head of its slot of T rows, in
    token order; the shared slots get every token; other rows zero."""
    T = cfg.tokens
    _, tok, slot, row, _ = unpack_route(cfg, slots, meta)
    xg = np.zeros((slots * T, cfg.hidden), np.int8)
    xg[slot * T + row] = x[tok]
    for s in range(slots - cfg.n_shared, slots):
        xg[s * T:(s + 1) * T] = x
    return xg


def swiglu_act(cfg: MoEConfig, gu: np.ndarray) -> np.ndarray:
    """int8 (n, 2f) gate|up -> int8 (n, f): clip((silu_q[g] * u) >>
    act_shift)."""
    f = cfg.expert_width
    a = silu_table(cfg)[gu[:, :f].astype(np.int64) + 128]
    return _requant(a * gu[:, f:].astype(np.int64), cfg.act_shift)


def act(cfg: MoEConfig, slots: int, gu: np.ndarray,
        meta: np.ndarray) -> np.ndarray:
    """:func:`swiglu_act` over each slot's live rows; other rows zero."""
    T = cfg.tokens
    h = np.zeros((slots * T, cfg.expert_width), np.int8)
    for s, n in enumerate(meta[:slots].astype(np.int64)):
        h[s * T:s * T + n] = swiglu_act(cfg, gu[s * T:s * T + n])
    return h


def routed_sum(cfg: MoEConfig, slots: int, yd: np.ndarray,
               meta: np.ndarray) -> np.ndarray:
    """sum over the held pairs of gate_q * expert output, int64 (T, d)."""
    _, tok, slot, row, gate = unpack_route(cfg, slots, meta)
    acc = np.zeros((cfg.tokens, cfg.hidden), np.int64)
    np.add.at(acc, tok, gate[:, None] * yd[slot * cfg.tokens + row])
    return acc


def shared_sum(cfg: MoEConfig, slots: int, yd: np.ndarray) -> np.ndarray:
    """The shared experts' outputs, summed, int64 (T, d)."""
    T = cfg.tokens
    return sum(yd[s * T:(s + 1) * T].astype(np.int64)
               for s in range(slots - cfg.n_shared, slots))


def finish(cfg: MoEConfig, routed: np.ndarray,
           shared: np.ndarray) -> np.ndarray:
    """The layer's int8 output from the int64 routed sum and the summed
    shared outputs."""
    return _requant(routed + (shared.astype(np.int64) << cfg.gate_frac),
                    cfg.out_shift)


def combine(cfg: MoEConfig, slots: int, yd: np.ndarray,
            meta: np.ndarray) -> np.ndarray:
    return finish(cfg, routed_sum(cfg, slots, yd, meta),
                  shared_sum(cfg, slots, yd))


# ----------------------------------------------------------------------
# the layer on the compiled path
# ----------------------------------------------------------------------
def _fn_key(name: str, cfg: MoEConfig, held: Sequence[int],
            bias: np.ndarray) -> str:
    """The host steps' compile-cache key: what their fns close over."""
    return (f"{name}.{cfg}.{[int(e) for e in held]}."
            + hashlib.sha1(np.asarray(bias, np.int32).tobytes()).hexdigest())


def expert_outputs(p: Program, x, cfg: MoEConfig, held: Sequence[int],
                   w: MoEWeights, name: str = "moe"):
    """Add one MoE layer holding routed experts `held` (slot order) to
    `p`, on its int8 (tokens, hidden) input `x`, up to the experts'
    outputs: steps 1-5 of the module docstring.  Returns ``(yd, meta)``:
    int8 (slots * tokens, hidden), each slot's outputs at the head of its
    ``tokens`` rows (shared slots last), and the routing table
    (:func:`route`), which :func:`combine` takes."""
    held = [int(e) for e in held]
    T, d, f = cfg.tokens, cfg.hidden, cfg.expert_width
    S = len(held) + cfg.n_shared
    if w.gate_up.shape != (S, 2 * f, d) or w.down.shape != (S, d, f):
        raise ValueError(f"weights are not for {len(held)} held + "
                         f"{cfg.n_shared} shared experts")
    bias = np.asarray(w.bias, np.int32)
    key = _fn_key(name, cfg, held, w.bias)
    logits = p.matmul(x, p.constant("w_router", w.router),
                      epilogue=Epilogue(shift=cfg.router_shift),
                      name=f"{name}_router")
    meta = p.host(lambda lg: route(cfg, held, lg, bias), logits,
                  shape=(cfg.meta_len(S),), kind="vec", dtype="int32",
                  name=f"{name}_route", key=f"route.{key}", span="moe.route")
    xg = p.host(lambda xv, m: dispatch(cfg, S, xv, m), x, meta,
                shape=(S * T, d), kind="mat", name=f"{name}_dispatch",
                key=f"dispatch.{key}", span="moe.route")
    gu = p.expert_matmul(xg, p.constant("w_gate_up",
                                        w.gate_up.reshape(S * 2 * f, d)),
                         meta, slots=S, routed=len(held),
                         epilogue=Epilogue(shift=cfg.gate_up_shift),
                         name=f"{name}_gate_up")
    h = p.host(lambda g, m: act(cfg, S, g, m), gu, meta, shape=(S * T, f),
               kind="mat", name=f"{name}_act", key=f"act.{key}",
               span="moe.act")
    yd = p.expert_matmul(h, p.constant("w_down", w.down.reshape(S * d, f)),
                         meta, slots=S, routed=len(held),
                         epilogue=Epilogue(shift=cfg.down_shift),
                         name=f"{name}_down")
    return yd, meta


def build_layer(cfg: MoEConfig, held: Sequence[int], w: MoEWeights,
                spec=None, name: str = "moe") -> Program:
    """One MoE layer holding routed experts `held` (slot order), input
    ``x`` int8 (tokens, hidden), output ``y`` int8 (tokens, hidden)."""
    p = Program(spec or _hwspec.tpu_like())
    T, d = cfg.tokens, cfg.hidden
    yd, meta = expert_outputs(p, p.input("x", (T, d)), cfg, held, w, name)
    S = len(held) + cfg.n_shared
    p.output(p.host(lambda y, m: combine(cfg, S, y, m), yd, meta,
                    shape=(T, d), kind="mat", name="y",
                    key=f"combine.{_fn_key(name, cfg, held, w.bias)}",
                    span="moe.combine"))
    return p


# ----------------------------------------------------------------------
# references
# ----------------------------------------------------------------------
def _gemm(a: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Exact int8 GEMM a @ w^T in int64 (float64 sums of int8 products
    over at most 2**20 terms are exact)."""
    return (a.astype(np.float64) @ w.astype(np.float64).T).astype(np.int64)


def _ffn(cfg: MoEConfig, x: np.ndarray, w_gu: np.ndarray,
         w_down: np.ndarray) -> np.ndarray:
    gu = _requant(_gemm(x, w_gu), cfg.gate_up_shift)
    return _requant(_gemm(swiglu_act(cfg, gu), w_down), cfg.down_shift)


def reference_parts(cfg: MoEConfig, held: Sequence[int], w: MoEWeights,
                    x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """The integer oracle's (routed int64 sum, summed shared outputs),
    computed expert by expert from the same rules."""
    held = list(held)
    top, gate = select(cfg, _requant(_gemm(x, w.router), cfg.router_shift),
                       w.bias)
    routed = np.zeros((cfg.tokens, cfg.hidden), np.int64)
    for s, e in enumerate(held):
        tok, k = np.nonzero(top == e)
        if tok.size:
            y = _ffn(cfg, x[tok], w.gate_up[s], w.down[s])
            routed[tok] += gate[tok, k][:, None] * y
    shared = sum(_ffn(cfg, x, w.gate_up[s], w.down[s]).astype(np.int64)
                 for s in range(len(held), len(held) + cfg.n_shared))
    return routed, shared


def reference_int(cfg: MoEConfig, held: Sequence[int], w: MoEWeights,
                  x: np.ndarray) -> np.ndarray:
    """The integer oracle: the layer's int8 output."""
    return finish(cfg, *reference_parts(cfg, held, w, x))


#: the float32 comparison (:func:`f32_error`): the median over tokens of
#: mean |y_int - y_f32| over rms(y_f32), per token.  The int8
#: requantizations (floor and 256 levels at each of the logits, gate|up,
#: h, the expert outputs and y), the score and SiLU tables and the Q12
#: gates read 0.04-0.07 at the test size and near 0.05 at JoyAI's widths;
#: a routing flip from the int8 logits changes a few tokens and moves the
#: median little.  The same layer with its weights rounded to int4 reads
#: 0.10-0.12 at both sizes
F32_TOLERANCE = 0.085


def reference_f32(cfg: MoEConfig, held: Sequence[int], w: MoEWeights,
                  x: np.ndarray) -> np.ndarray:
    """The published block in float32 ``jax.numpy``, for the share
    `held`: sigmoid scores, ``noaux_tc`` selection by score plus bias, the
    k weights normalized (``norm_topk_prob``) and scaled, SwiGLU experts,
    shared experts added with weight one.  Inputs and weights are the
    int8 tensors read at the scales the fixed-point rules give them;
    nothing is rounded or clipped.  Returns float32 (T, d) at the
    output's int8 scale."""
    import jax
    import jax.numpy as jnp

    f = cfg.expert_width
    held = list(held)
    with jax.default_matmul_precision("highest"):
        xf = jnp.asarray(x, jnp.float32)

        def lin(a, wt, shift):
            return (a @ jnp.asarray(wt, jnp.float32).T) / 2.0 ** shift

        def expert(xs, s):
            gu = lin(xs, w.gate_up[s], cfg.gate_up_shift)
            g = gu[:, :f] / 2.0 ** cfg.act_frac
            a = jax.nn.silu(g) * 2.0 ** cfg.act_frac
            return lin(a * gu[:, f:] / 2.0 ** cfg.act_shift, w.down[s],
                       cfg.down_shift)

        score = jax.nn.sigmoid(lin(xf, w.router, cfg.router_shift)
                               / 2.0 ** cfg.logit_frac)
        sel = score + jnp.asarray(w.bias, jnp.float32) / 2.0 ** cfg.score_frac
        _, top = jax.lax.top_k(sel, cfg.top_k)
        s = jnp.take_along_axis(score, top, axis=1)
        gate = s / s.sum(axis=1, keepdims=True) * cfg.routed_scaling
        y = sum(expert(xf, s_) for s_ in
                range(len(held), len(held) + cfg.n_shared))
        for slot, e in enumerate(held):
            wt = jnp.where(top == e, gate, 0.0).sum(axis=1)
            y = y + wt[:, None] * expert(xf, slot)
        return np.asarray(y / 2.0 ** (cfg.out_shift - cfg.gate_frac))


def f32_error(y_int: np.ndarray, y_f32: np.ndarray) -> float:
    """Median over tokens of |y_int - y_f32| / rms(y_f32) per token."""
    err = np.abs(y_int.astype(np.float64) - y_f32).mean(axis=1)
    rms = np.sqrt((y_f32.astype(np.float64) ** 2).mean(axis=1))
    return float(np.median(err / np.maximum(rms, 1e-9)))


def lower_precision(w: MoEWeights, bits: int) -> MoEWeights:
    """Every weight rounded to a `bits`-bit grid over the int8 range."""
    step = 1 << (8 - bits)
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1

    def q(a):
        return (np.clip(np.rint(a / step), lo, hi) * step).astype(np.int8)
    return MoEWeights(router=q(w.router), bias=w.bias,
                      gate_up=q(w.gate_up), down=q(w.down))
