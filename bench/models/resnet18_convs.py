"""ResNet-18's offloaded conv layers as the benchmark runs them.

A configuration of this model (``bench/configs/resnet18.*.json``) lists
the conv layers' published shapes (``layers``) and the network's calls in
order (``calls``).  Every call is its own ``Program.conv2d`` with its own
seeded int8 weights, so the weight working set is the network's.  One
request is one image: its calls in order, each on a seeded input of its
own shape.

What the harness asks of a model module:

* ``make_data(cfg, seed, n_sets)``: weights and ``n_sets`` input sets,
  made on the device in one jitted call from the seed;
* ``build(cfg, data)``: the system under test's programs, one per call;
* ``request(data, set_idx)``: one request's per-call inputs;
* ``call_names(cfg)`` and ``call_work(cfg)``: names and useful work
  (operations and bytes from the shapes) of each call;
* ``reference(cfg, data, set_idx, wgt_bits)``: the plain reference's
  outputs for one input set (``wgt_bits`` below the configuration's is
  the control).
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

import resnet18_convs_ref as ref


@dataclass(frozen=True)
class Work:
    """Useful work of one call, from its shapes alone."""
    macs: int
    weight_bytes: int      # int8 weights
    in_bytes: int          # int8 input activation
    out_bytes: int         # int8 output activation

    @property
    def ops(self) -> int:
        return 2 * self.macs

    def gang_bytes(self, gang: int) -> int:
        """Least HBM traffic of a gang of `gang` such calls: the weights
        once, each member's input and output."""
        return self.weight_bytes + gang * (self.in_bytes + self.out_bytes)


def call_names(cfg: Dict) -> List[str]:
    return [c["layer"] for c in cfg["calls"]]


def call_work(cfg: Dict) -> List[Work]:
    out = []
    for c in cfg["calls"]:
        ly = cfg["layers"][c["layer"]]
        g = ref.conv_shape(ly)
        b = cfg["batch"]
        k2 = ly["k"] * ly["k"]
        out.append(Work(
            macs=b * ly["oc"] * g["oh"] * g["ow"] * ly["ic"] * k2,
            weight_bytes=ly["oc"] * ly["ic"] * k2,
            in_bytes=b * ly["ic"] * ly["h"] * ly["h"],
            out_bytes=b * ly["oc"] * g["oh"] * g["ow"]))
    return out


def _key_from_seed(seed: int) -> int:
    """A 31-bit PRNG key for any whole-number seed (the driver's seeds
    exceed 32 signed bits)."""
    h = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(h[:4], "little") >> 1


def make_data(cfg: Dict, seed: int, n_sets: int) -> Dict:
    """Weights (one per call) and `n_sets` input sets, drawn uniformly
    over int8 on the device in ONE jitted call (one flat draw, cut into
    the tensors on the host), then kept on the host where the programs
    stage them."""
    import jax
    import jax.numpy as jnp

    lo, hi = cfg["value_range"]
    b = cfg["batch"]
    wshapes, xshapes = [], []
    for c in cfg["calls"]:
        ly = cfg["layers"][c["layer"]]
        wshapes.append((ly["oc"], ly["ic"], ly["k"], ly["k"]))
        xshapes.append((b, ly["ic"], ly["h"], ly["h"]))
    shapes = wshapes + xshapes * n_sets
    sizes = [int(np.prod(s)) for s in shapes]
    flat = np.asarray(jax.jit(
        lambda key: jax.random.randint(key, (sum(sizes),), lo, hi + 1,
                                       dtype=jnp.int8))(
        jax.random.key(_key_from_seed(seed))))
    arrs = [a.reshape(s) for a, s in
            zip(np.split(flat, np.cumsum(sizes)[:-1]), shapes)]
    n = len(wshapes)
    return {"weights": arrs[:n],
            "inputs": [arrs[n + i * n:n + (i + 1) * n]
                       for i in range(n_sets)]}


def build(cfg: Dict, data: Dict) -> list:
    """One ``Program.conv2d`` per call on the configuration's template
    instance, with the call's constant weights and requant epilogue."""
    from repro.core import hwspec
    from repro.core.conv import ConvShape
    from repro.core.program import Program
    from repro.core.scheduler import Epilogue

    spec = getattr(hwspec, cfg["template"])()
    progs = []
    for i, (c, w) in enumerate(zip(cfg["calls"], data["weights"])):
        ly = cfg["layers"][c["layer"]]
        k = ly["k"]
        s = ConvShape(n=cfg["batch"], h=ly["h"], w=ly["h"], ic=ly["ic"],
                      oc=ly["oc"], kh=k, kw=k, stride=ly["stride"],
                      pad=k // 2)
        p = Program(spec)
        x = p.input("x", (s.n, s.ic, s.h, s.w))
        p.output(p.conv2d(x, p.constant("w", w), s,
                          epilogue=Epilogue(shift=ly["shift"],
                                            relu=c["relu"]),
                          name=f"{c['layer']}_{i}"))
        progs.append(p)
    return progs


def request(data: Dict, set_idx: int) -> List[Dict[str, np.ndarray]]:
    return [{"x": x} for x in data["inputs"][set_idx]]


def reference(cfg: Dict, data: Dict, set_idx: int,
              wgt_bits: int = 8) -> List[np.ndarray]:
    return ref.image(cfg, data["weights"], data["inputs"][set_idx],
                     wgt_bits)
