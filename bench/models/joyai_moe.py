"""JoyAI-LLM-Flash's routed MoE layers as the benchmark runs them.

A configuration of this model (``bench/configs/joyai_flash.*.json``)
keeps the published config's numbers and adds the chip's share: the
``routed_experts`` it holds (experts ``routed_experts * ep_rank`` on),
the ``depth`` of MoE layers run, the ``tokens`` of one decode step and
the ``fixed_point`` rules.  Each layer is one program
(``repro.models.vta_moe.build_layer``) with its own seeded int8 weights
and correction bias; one request is one decode step: the layers' calls
in order, each on a seeded input of its own.

The harness's contract is the one ``resnet18_convs`` states: ``make_data``,
``build``, ``request``, ``call_names``, ``call_work`` and ``reference``.
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

import joyai_moe_ref as ref
from resnet18_convs import Work


def held(cfg: Dict) -> List[int]:
    n = cfg["routed_experts"]
    return list(range(n * cfg["ep_rank"], n * (cfg["ep_rank"] + 1)))


def call_names(cfg: Dict) -> List[str]:
    return [f"moe{layer + 1}" for layer in range(cfg["depth"])]


def call_work(cfg: Dict) -> List[Work]:
    """Per layer call: the router and the shared experts in full, the
    held routed experts at the expected tokens * k * held / n_routed
    token-expert pairs (whatever the routing was); weights of the router,
    the shared and every held expert."""
    T, d = cfg["tokens"], cfg["hidden_size"]
    f, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    ffn = 3 * d * f
    pairs = T * cfg["num_experts_per_tok"] * cfg["routed_experts"] // E
    macs = T * d * E + T * ffn * cfg["n_shared_experts"] + pairs * ffn
    wbytes = E * d + ffn * (cfg["n_shared_experts"] + cfg["routed_experts"])
    return [Work(macs=macs, weight_bytes=wbytes, in_bytes=T * d,
                 out_bytes=T * d)] * cfg["depth"]


def make_data(cfg: Dict, seed: int, n_sets: int) -> Dict:
    """Per layer: the router, the correction bias and the held and shared
    experts' weights; per input set and layer: tokens around the layer's
    seeded topic centroids, each set with its own topic popularity
    (Dirichlet(1), as normalized exponentials); the skew's parameters
    and the bias's span are the configuration's ``assumed``.  Drawn on
    the host in bulk from the seed (numpy draws int8 at a few GB/s, and
    the programs stage their weights from the host)."""
    rng = np.random.default_rng(int(seed))
    L, T, d = cfg["depth"], cfg["tokens"], cfg["hidden_size"]
    f, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    S = cfg["routed_experts"] + cfg["n_shared_experts"]
    a = cfg["assumed"]
    k, bs, c = a["topics"], a["bias_span"], a["centroid"]

    def i8(*shape):
        return rng.integers(-128, 128, shape, dtype=np.int8)
    layers = [{"router": i8(E, d),
               "bias": rng.integers(-bs, bs + 1, E).astype(np.int32),
               "gate_up": i8(S, 2 * f, d), "down": i8(S, d, f)}
              for _ in range(L)]
    cent = rng.integers(-c, c + 1, (L, k, d), dtype=np.int16)
    pop = rng.exponential(size=(n_sets, L, k)).cumsum(axis=-1)
    u = rng.random((n_sets, L, T)) * pop[..., -1:]
    topic = np.minimum((u[..., None] >= pop[:, :, None, :]).sum(-1), k - 1)
    noise = rng.integers(-a["noise"], a["noise"] + 1,
                         (n_sets, L, T, d), dtype=np.int16)
    x = np.clip(cent[np.arange(L)[None, :, None], topic] + noise,
                -128, 127).astype(np.int8)
    return {"layers": layers, "inputs": [list(x[s]) for s in range(n_sets)]}


def _moe_config(cfg: Dict):
    from repro.models.vta_moe import MoEConfig

    if not (cfg["norm_topk_prob"] and cfg["scoring_func"] == "sigmoid"
            and cfg["topk_method"] == "noaux_tc" and cfg["n_group"] == 1):
        raise ValueError("vta_moe implements the sigmoid, noaux_tc, "
                         "normalized top-k router with one group")
    return MoEConfig(hidden=cfg["hidden_size"],
                     expert_width=cfg["moe_intermediate_size"],
                     n_experts=cfg["n_routed_experts"],
                     top_k=cfg["num_experts_per_tok"],
                     n_shared=cfg["n_shared_experts"],
                     routed_scaling=cfg["routed_scaling_factor"],
                     tokens=cfg["tokens"], **cfg["fixed_point"])


def build(cfg: Dict, data: Dict) -> list:
    """One MoE layer program per call on the configuration's template,
    holding this chip's experts."""
    from repro.core import hwspec
    from repro.models.vta_moe import MoEWeights, build_layer

    spec = getattr(hwspec, cfg["template"])()
    mcfg = _moe_config(cfg)
    return [build_layer(mcfg, held(cfg), MoEWeights(**w), spec,
                        name=name)
            for name, w in zip(call_names(cfg), data["layers"])]


def request(data: Dict, set_idx: int) -> List[Dict[str, np.ndarray]]:
    return [{"x": x} for x in data["inputs"][set_idx]]


def reference(cfg: Dict, data: Dict, set_idx: int,
              wgt_bits: int = 8) -> List[np.ndarray]:
    """The plain reference's outputs for one input set, one per layer;
    logs each call's load on the held experts (max / mean rows)."""
    outs = []
    for name, w, x in zip(call_names(cfg), data["layers"],
                          data["inputs"][set_idx]):
        y, load = ref.layer(cfg, held(cfg), w, x, wgt_bits)
        outs.append(y)
        mean = load.mean()
        print(f"set {set_idx} {name}: held-expert rows {load.tolist()}, "
              f"max/mean {load.max() / mean if mean else 0.0:.2f}",
              file=sys.stderr, flush=True)
    return outs
