"""Plain reference for the ``resnet18_convs`` model: numpy only.

It imports nothing of the system under test.  Each conv is an exact
integer conv2d computed as an im2col matrix product in float64 (every
partial sum of int8 x int8 products over at most 4608 terms stays far
below 2**53, so the float64 sum is the integer sum), followed by the
configuration's epilogue: arithmetic shift right, relu where the call
has one, clip to int8.

``wgt_bits`` below 8 is the control: the weights are first rounded to a
``wgt_bits``-bit grid over the same int8 range (int4 for int8), which is
what computing the layer one precision lower would give.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def conv_shape(layer: Dict) -> Dict[str, int]:
    """Output geometry of one layer entry of the configuration ("SAME"
    padding: k // 2)."""
    h, k, s = layer["h"], layer["k"], layer["stride"]
    pad = k // 2
    oh = (h + 2 * pad - k) // s + 1
    return dict(pad=pad, oh=oh, ow=oh)


def lower_precision(w: np.ndarray, wgt_bits: int) -> np.ndarray:
    """Round int8 weights to a `wgt_bits`-bit grid spanning int8."""
    if wgt_bits >= 8:
        return w
    step = 1 << (8 - wgt_bits)
    lo, hi = -(1 << (wgt_bits - 1)), (1 << (wgt_bits - 1)) - 1
    return (np.clip(np.round(w.astype(np.float64) / step), lo, hi)
            * step).astype(np.int64)


def conv2d(x: np.ndarray, w: np.ndarray, layer: Dict, relu: bool,
           wgt_bits: int = 8) -> np.ndarray:
    """x (1, ic, h, h) int8, w (oc, ic, k, k) int8 -> (1, oc, oh, ow) int8."""
    g = conv_shape(layer)
    k, s, pad = layer["k"], layer["stride"], g["pad"]
    xp = np.pad(x[0].astype(np.float64), ((0, 0), (pad, pad), (pad, pad)))
    win = np.lib.stride_tricks.sliding_window_view(xp, (k, k), axis=(1, 2))
    win = win[:, ::s, ::s][:, :g["oh"], :g["ow"]]      # (ic, oh, ow, k, k)
    cols = win.transpose(0, 3, 4, 1, 2).reshape(-1, g["oh"] * g["ow"])
    wm = lower_precision(w, wgt_bits).reshape(w.shape[0], -1)
    acc = (wm.astype(np.float64) @ cols).astype(np.int64)
    acc >>= layer["shift"]
    if relu:
        acc = np.maximum(acc, 0)
    out = np.clip(acc, -128, 127).astype(np.int8)
    return out.reshape(1, w.shape[0], g["oh"], g["ow"])


def image(cfg: Dict, weights: List[np.ndarray], inputs: List[np.ndarray],
          wgt_bits: int = 8) -> List[np.ndarray]:
    """The 20 (or however many the configuration lists) outputs of one
    image, in call order."""
    return [conv2d(x, w, cfg["layers"][c["layer"]], c["relu"], wgt_bits)
            for c, w, x in zip(cfg["calls"], weights, inputs)]
