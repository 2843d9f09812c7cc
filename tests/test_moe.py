"""The routed MoE layer (``models/vta_moe.py``) on the compiled path, at a
small size: d 256, expert width 128, 16 routed experts with 4 held, top-4,
1 shared expert, 32 tokens a call, on ``tpu_like()`` (batch 8).

  * bit-exact against the integer oracle on both engines, and within the
    stated tolerance of the float32 block, which int4 weights are not;
  * the four disjoint shares' routed sums plus the shared expert, counted
    once, give the uncut layer;
  * an expert with no rows launches nothing, a repeated routing replays
    the streams the JIT emitted for it, and a pool gang of calls with
    different routings returns each call its own answer.
"""
import numpy as np
import pytest

from repro.core import hwspec
from repro.core.backend import decode_cache_info, set_decode_cache_cap
from repro.core.program import Program
from repro.core.serve import DevicePool, WatchdogConfig
from repro.models import vta_moe as M

CFG = M.MoEConfig(hidden=256, expert_width=128, n_experts=16, top_k=4,
                  n_shared=1, tokens=32, router_shift=11, gate_up_shift=11,
                  act_shift=6, down_shift=9)
HELD = [2, 5, 9, 14]
SPEC = hwspec.tpu_like()


def _x(seed: int) -> np.ndarray:
    """Tokens drawn around a few seeded topic centroids, with int8 noise,
    so that routing is uneven."""
    rng = np.random.default_rng(seed)
    c = rng.integers(-56, 57, (3, CFG.hidden))
    topic = rng.integers(0, 3, CFG.tokens)
    return np.clip(c[topic] + rng.integers(-100, 101, (CFG.tokens,
                                                       CFG.hidden)),
                   -128, 127).astype(np.int8)


def _weights(held=HELD, seed=0) -> M.MoEWeights:
    return M.random_weights(CFG, held, np.random.default_rng(seed))


def _compile(w, held=HELD):
    return M.build_layer(CFG, held, w, SPEC).compile(use_cache=False)


def _compile_share(w, held):
    """The layer holding `held`, with the int32 sum over its held pairs
    before the final requant (``routed``) and the summed shared outputs
    (``shared``) as its outputs: what shares of one layer add up."""
    T, d, S = CFG.tokens, CFG.hidden, len(held) + CFG.n_shared
    p = Program(SPEC)
    yd, meta = M.expert_outputs(p, p.input("x", (T, d)), CFG, held, w)
    p.output(p.host(lambda y, m: M.routed_sum(CFG, S, y, m).astype(np.int32),
                    yd, meta, shape=(T, d), kind="mat", dtype="int32",
                    name="routed"))
    p.output(p.host(lambda y: M.shared_sum(CFG, S, y).astype(np.int32), yd,
                    shape=(T, d), kind="mat", dtype="int32", name="shared"))
    return p.compile(use_cache=False)


def _expert_stats(stats):
    return [s for s in stats if s.moe_experts_hit]


@pytest.mark.parametrize("backend", ["simulator", "pallas"])
def test_bit_exact_against_the_integer_oracle(backend):
    w = _weights()
    c = _compile(w)
    for seed in (1, 2):
        x = _x(seed)
        assert np.array_equal(c(backend=backend, x=x),
                              M.reference_int(CFG, HELD, w, x))
    steps = c.expert_steps
    assert len(steps) == 2 and all(s.routed == 4 and s.slots == 5
                                   for s in steps)


def test_within_tolerance_of_float32_and_int4_weights_are_not():
    w = _weights()
    x = _x(3)
    want = M.reference_f32(CFG, HELD, w, x)
    err8 = M.f32_error(_compile(w)(x=x), want)
    err4 = M.f32_error(_compile(M.lower_precision(w, 4))(x=x), want)
    assert err8 <= M.F32_TOLERANCE < err4, (err8, err4)


def test_four_disjoint_shares_add_up_to_the_uncut_layer():
    full = _weights(held=list(range(16)), seed=4)
    x = _x(4)
    routed = np.zeros((CFG.tokens, CFG.hidden), np.int64)
    shared = None
    for i in range(4):
        held = list(range(4 * i, 4 * i + 4))
        slots = held + [16]
        share = M.MoEWeights(full.router, full.bias, full.gate_up[slots],
                             full.down[slots])
        out = _compile_share(share, held)(x=x)
        assert out["routed"].dtype == np.int32
        routed += out["routed"]
        if shared is None:
            shared = out["shared"]
        assert np.array_equal(out["shared"], shared)
    uncut = _compile(full, list(range(16)))(x=x)
    assert np.array_equal(M.finish(CFG, routed, shared), uncut)
    assert np.array_equal(uncut, M.reference_int(CFG, range(16), full, x))


def test_an_expert_with_no_rows_launches_nothing():
    w = _weights()
    bias = w.bias.copy()
    bias[HELD[1]] = -(1 << 24)            # never among the top 4
    w = M.MoEWeights(w.router, bias, w.gate_up, w.down)
    c = _compile(w)
    x = _x(5)
    y = c(x=x)
    assert np.array_equal(y, M.reference_int(CFG, HELD, w, x))
    hits = _expert_stats(c.last_stats)
    assert hits and all(s.moe_rows > 0 for s in hits)
    for step in c.expert_steps:
        assert {slot for slot, _ in step.variants} == {0, 2, 3, 4}
    # one launch per held expert with rows, and the shared one, per step
    assert len(c.last_stats) == 1 + 2 * 4
    for s in hits:
        assert s.moe_rows_padded == -(-s.moe_rows // SPEC.batch) * SPEC.batch
        assert s.moe_load_max == max(h.moe_rows for h in hits)


def test_a_repeated_routing_replays_its_streams():
    cap = decode_cache_info()["cap"]
    set_decode_cache_cap(0)          # forget plans other tests recorded
    set_decode_cache_cap(cap)
    w = _weights()
    c = _compile(w)
    x = _x(6)
    c(backend="pallas", x=x)
    emitted = [len(s.variants) for s in c.expert_steps]
    first = c.last_stats
    assert sum(emitted) == len(first) - 1      # every expert launch new
    assert not any(s.plan_hit for s in _expert_stats(first))
    assert set(first[-1].host_span_s) == {"moe.route", "moe.act",
                                          "moe.combine"}
    assert not any(s.host_span_s for s in first[:-1])
    c(backend="pallas", x=x)
    assert [len(s.variants) for s in c.expert_steps] == emitted
    assert all(s.plan_hit == 1 for s in c.last_stats)
    other = _x(7)
    assert np.array_equal(c(backend="pallas", x=other),
                          M.reference_int(CFG, HELD, w, other))


def test_a_gang_with_different_routings_answers_each_call():
    w = _weights()
    c = _compile(w)
    xs = [_x(8), _x(8), _x(9)]
    # the watchdog prices each expert stream a round runs
    with DevicePool(c, size=3, backend="pallas",
                    watchdog=WatchdogConfig(floor_s=300.0)) as pool:
        futs = pool.submit_batch(None, [{"x": x} for x in xs])
        outs = [f.wait(timeout=600) for f in futs]
    for x, y in zip(xs, outs):
        assert np.array_equal(y, M.reference_int(CFG, HELD, w, x))
    for f in futs:
        assert set(f.stats[-1].host_span_s) == {"moe.route", "moe.act",
                                                "moe.combine"}
    same = [_expert_stats(f.stats) for f in futs[:2]]
    assert all(s.gang_size >= 2 for s in same[0] + same[1])
    rows = [[(s.moe_rows, s.moe_rows_padded) for s in st] for st in same]
    assert rows[0] == rows[1]
    _, tok, slot, _, _ = M.unpack_route(
        CFG, 5, M.route(CFG, HELD, M._requant(M._gemm(xs[2], w.router),
                                              CFG.router_shift), w.bias))
    want = np.bincount(slot, minlength=4)
    got = [s.moe_rows for s in _expert_stats(futs[2].stats)]
    assert sorted(got) == sorted(2 * [int(n) for n in want if n])


def test_merged_sums_expert_counters_and_host_spans():
    from repro.core.simulator import RunStats

    a = RunStats(moe_rows=3, moe_rows_padded=8, moe_experts_hit=1,
                 moe_load_max=5, host_span_s={"moe.route": 0.5})
    b = RunStats(moe_rows=9, moe_rows_padded=16, moe_experts_hit=1,
                 moe_load_max=9, host_span_s={"moe.route": 0.25,
                                              "moe.combine": 1.0})
    m = RunStats.merged([a, b])
    assert (m.moe_rows, m.moe_rows_padded, m.moe_experts_hit,
            m.moe_load_max) == (12, 24, 2, 9)
    assert m.host_span_s == {"moe.route": 0.75, "moe.combine": 1.0}
    assert a.host_span_s == {"moe.route": 0.5}
