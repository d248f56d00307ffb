"""The cell's operand traffic, made by the benchmark itself in NumPy.

One inference of the configuration's network on the seed's glyph image,
from the trained weights kept beside the benchmark (``weights/<model>.npz``).
Each layer gives one (inputs, weights) matrix pair, one row per neuron, in
which column j of the inputs is the value that column j of the weights
multiplies: a row's dot product plus the bias is the neuron's
pre-activation.

- conv (VALID, stride 1): neuron = (output channel, output position), the
  channels outermost and the positions row-major; k = (kh, kw, cin) in the
  HWIO kernel's own order, for the inputs and the weights alike.
- dense: neuron = output unit; the inputs row is the layer's input vector.

These operands go to the program's sweep entry and to the plain reference
alike, so that nothing the reference reads is made by the program.
"""
from __future__ import annotations

import os
from typing import Dict, List, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))

Layer = Tuple[np.ndarray, np.ndarray]


def load_weights(config: dict) -> Dict[str, np.ndarray]:
    """The configuration's trained weights, by the names its layers use."""
    path = os.path.join(BENCH, "weights", f"{config['model']}.npz")
    with np.load(path) as z:
        return {k.split("/", 1)[-1]: np.asarray(z[k], np.float32)
                for k in z.files if k.startswith("params/")}


def patches(x: np.ndarray, kh: int, kw: int) -> np.ndarray:
    """(oh * ow, kh * kw * cin) receptive fields of a VALID conv over
    ``x`` (H, W, cin), positions row-major, k in (kh, kw, cin) order."""
    h, w, c = x.shape
    oh, ow = h - kh + 1, w - kw + 1
    cols = np.stack([x[i:i + oh, j:j + ow, :] for i in range(kh)
                     for j in range(kw)], axis=2)          # (oh, ow, kh*kw, c)
    return cols.reshape(oh * ow, kh * kw * c)


def _act(y: np.ndarray, name) -> np.ndarray:
    if name is None:
        return y
    if name == "tanh":
        return np.tanh(y)
    if name == "leaky0.1":
        return np.where(y >= 0, y, np.float32(0.1) * y)
    raise KeyError(f"no activation {name!r}")


def _pool(x: np.ndarray, k: int) -> np.ndarray:
    """k x k max pool, stride k, VALID (the remainder rows are dropped)."""
    h, w, c = x.shape
    oh, ow = h // k, w // k
    return x[:oh * k, :ow * k].reshape(oh, k, ow, k, c).max(axis=(1, 3))


def layer_operands(config: dict, params: Dict[str, np.ndarray],
                   image: np.ndarray) -> List[Layer]:
    """Per layer of ``config["layers"]``, the (inputs, weights) pair of
    one inference on ``image`` (H, W, C) float32."""
    h = np.asarray(image, np.float32)
    out = []
    for spec in config["layers"]:
        w = params[spec["weight"]]
        b = params[spec["bias"]]
        if spec["op"] == "conv":
            kh, kw, cin, cout = w.shape
            pat = patches(h, kh, kw)
            wcol = w.reshape(kh * kw * cin, cout).T        # (cout, k)
            n = pat.shape[0]
            out.append((np.tile(pat, (cout, 1)), np.repeat(wcol, n, axis=0)))
            oh, ow = h.shape[0] - kh + 1, h.shape[1] - kw + 1
            y = (pat @ wcol.T + b).reshape(oh, ow, cout)
            y = _act(y, spec.get("act"))
            h = _pool(y, spec["pool"]) if spec.get("pool") else y
        elif spec["op"] == "dense":
            x = h.reshape(-1)
            wt = w.T                                        # (out, k)
            out.append((np.broadcast_to(x, wt.shape).copy(), wt.copy()))
            h = _act(x @ w + b, spec.get("act"))
        else:
            raise KeyError(f"no layer op {spec['op']!r}")
    return [(i.astype(np.float32), w.astype(np.float32)) for i, w in out]


def cell_operands(config: dict, seed: int) -> List[Layer]:
    """The operands of the cell's one inference on the seed's image."""
    from glyph import glyph_image
    inp = config["input"]
    img = glyph_image(seed, inp["hw"], inp["channels"])
    return layer_operands(config, load_weights(config), img)
