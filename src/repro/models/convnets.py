"""LeNet and a DarkNet-like CNN - the paper's evaluated DNN workloads.

These run forward *and* training on CPU (the paper uses trained LeNet
weights), and expose ``layer_traffic()`` which turns one inference into the
(input, weight) operand streams the NoC injects (Sec. V-B). Float32
end-to-end; the fixed-8 path quantizes at the memory controller
(repro.quant).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .spec import ParamSpec
from repro.noc.traffic import LayerTraffic, conv_layer_traffic, linear_layer_traffic

_F32 = jnp.float32

__all__ = ["LeNet", "DarkNetLike"]


def _conv(x, w, b, stride=1):
    y = jax.lax.conv_general_dilated(
        x[None] if x.ndim == 3 else x, w, (stride, stride), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = y + b
    return y[0] if x.ndim == 3 else y


def _on_host(extract):
    """Run a ``layer_traffic`` extraction in f32 on the host CPU and return
    its streams as uncommitted arrays on the default device.

    The streams are the data every backend packetizes and drains, so they
    must be the same bits on every platform. A TPU computes f32 convs and
    matmuls in bf16 passes at default precision, and even at the highest
    precision it sums in another order than the CPU; one activation that
    lands on the other side of a fixed-8 rounding step then moves BT.
    """
    @functools.wraps(extract)
    def wrapped(self, params, x):
        layers = extract(self, *jax.device_put((params, x),
                                               jax.devices("cpu")[0]))
        return [LayerTraffic(jnp.asarray(np.asarray(t.inputs)),
                             jnp.asarray(np.asarray(t.weights)))
                for t in layers]
    return wrapped


def _pool(x, k=2):
    nd = x.ndim
    if nd == 3:
        x = x[None]
    y = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                              (1, k, k, 1), (1, k, k, 1), "VALID")
    return y[0] if nd == 3 else y


class LeNet:
    """Classic LeNet-5: 32x32x1 -> conv6@5 -> pool -> conv16@5 -> pool
    -> fc120 -> fc84 -> fc10. ~61.7k parameters."""

    input_shape = (32, 32, 1)
    n_classes = 10

    def specs(self) -> dict:
        return {
            "c1w": ParamSpec((5, 5, 1, 6), (None, None, "conv_in", "conv_out"), dtype=_F32),
            "c1b": ParamSpec((6,), ("conv_out",), init="zeros", dtype=_F32),
            "c2w": ParamSpec((5, 5, 6, 16), (None, None, "conv_in", "conv_out"), dtype=_F32),
            "c2b": ParamSpec((16,), ("conv_out",), init="zeros", dtype=_F32),
            "f1w": ParamSpec((400, 120), ("mlp", "embed"), dtype=_F32),
            "f1b": ParamSpec((120,), ("embed",), init="zeros", dtype=_F32),
            "f2w": ParamSpec((120, 84), ("mlp", "embed"), dtype=_F32),
            "f2b": ParamSpec((84,), ("embed",), init="zeros", dtype=_F32),
            "f3w": ParamSpec((84, 10), ("mlp", "embed"), dtype=_F32),
            "f3b": ParamSpec((10,), ("embed",), init="zeros", dtype=_F32),
        }

    def activations(self, params, x: jax.Array) -> List[jax.Array]:
        """Per-layer INPUT activations for one image (H, W, C)."""
        acts = [x]
        h = jnp.tanh(_conv(x, params["c1w"], params["c1b"]))
        h = _pool(h)
        acts.append(h)
        h = jnp.tanh(_conv(h, params["c2w"], params["c2b"]))
        h = _pool(h)
        h = h.reshape(-1)
        acts.append(h)
        h = jnp.tanh(h @ params["f1w"] + params["f1b"])
        acts.append(h)
        h = jnp.tanh(h @ params["f2w"] + params["f2b"])
        acts.append(h)
        return acts

    def forward(self, params, x: jax.Array) -> jax.Array:
        """Batched forward: x (B, 32, 32, 1) -> logits (B, 10)."""
        h = jnp.tanh(_conv(x, params["c1w"], params["c1b"]))
        h = _pool(h)
        h = jnp.tanh(_conv(h, params["c2w"], params["c2b"]))
        h = _pool(h)
        h = h.reshape(h.shape[0], -1)
        h = jnp.tanh(h @ params["f1w"] + params["f1b"])
        h = jnp.tanh(h @ params["f2w"] + params["f2b"])
        return h @ params["f3w"] + params["f3b"]

    def loss(self, params, x, y):
        logits = self.forward(params, x)
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), y[:, None], axis=1))

    @_on_host
    def layer_traffic(self, params, x: jax.Array) -> List[LayerTraffic]:
        """The operand streams one inference injects into the NoC."""
        a = self.activations(params, x)
        return [
            conv_layer_traffic(a[0], params["c1w"]),
            conv_layer_traffic(a[1], params["c2w"]),
            linear_layer_traffic(a[2], params["f1w"].T),
            linear_layer_traffic(a[3], params["f2w"].T),
            linear_layer_traffic(a[4], params["f3w"].T),
        ]

    def weight_stream(self, params) -> jax.Array:
        """All weights as one flat stream, kernels zero-padded to flit-lane
        multiples (paper Sec. V-A protocol for the no-NoC study)."""
        ker1 = params["c1w"].transpose(3, 0, 1, 2).reshape(6, 25)
        ker2 = params["c2w"].transpose(3, 2, 0, 1).reshape(96, 25)
        parts = [
            jnp.pad(ker1, ((0, 0), (0, 7))).reshape(-1),
            jnp.pad(ker2, ((0, 0), (0, 7))).reshape(-1),
            params["f1w"].T.reshape(-1),
            params["f2w"].T.reshape(-1),
            jnp.pad(params["f3w"].T, ((0, 0), (0, 4))).reshape(-1),
        ]
        return jnp.concatenate(parts)


@dataclasses.dataclass(frozen=True)
class DarkNetLike:
    """DarkNet-reference-style CNN on 64x64x3 (paper Sec. V-B: input reduced
    to 64x64x3 'to speed up the simulation'). 3x3 convs doubling channels
    with maxpools, then a linear classifier head."""

    channels: Tuple[int, ...] = (16, 32, 64, 128)
    n_classes: int = 10
    input_shape = (64, 64, 3)

    def specs(self) -> dict:
        s = {}
        cin = 3
        for i, cout in enumerate(self.channels):
            s[f"c{i}w"] = ParamSpec((3, 3, cin, cout),
                                    (None, None, "conv_in", "conv_out"), dtype=_F32)
            s[f"c{i}b"] = ParamSpec((cout,), ("conv_out",), init="zeros", dtype=_F32)
            cin = cout
        # after len(channels) VALID convs + pools on 64x64: spatial ~2x2
        self_dim = self._head_dim()
        s["fw"] = ParamSpec((self_dim, self.n_classes), ("mlp", "embed"), dtype=_F32)
        s["fb"] = ParamSpec((self.n_classes,), ("embed",), init="zeros", dtype=_F32)
        return s

    def _head_dim(self) -> int:
        hw = 64
        for _ in self.channels:
            hw = (hw - 2) // 2
        return hw * hw * self.channels[-1]

    def activations(self, params, x: jax.Array) -> List[jax.Array]:
        acts = [x]
        h = x
        for i in range(len(self.channels)):
            h = jax.nn.leaky_relu(_conv(h, params[f"c{i}w"], params[f"c{i}b"]), 0.1)
            h = _pool(h)
            if i < len(self.channels) - 1:
                acts.append(h)
        acts.append(h.reshape(-1))
        return acts

    def forward(self, params, x: jax.Array) -> jax.Array:
        h = x
        for i in range(len(self.channels)):
            h = jax.nn.leaky_relu(_conv(h, params[f"c{i}w"], params[f"c{i}b"]), 0.1)
            h = _pool(h)
        h = h.reshape(h.shape[0], -1)
        return h @ params["fw"] + params["fb"]

    def loss(self, params, x, y):
        logits = self.forward(params, x)
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), y[:, None], axis=1))

    @_on_host
    def layer_traffic(self, params, x: jax.Array) -> List[LayerTraffic]:
        a = self.activations(params, x)
        out = []
        for i in range(len(self.channels)):
            out.append(conv_layer_traffic(a[i], params[f"c{i}w"]))
        out.append(linear_layer_traffic(a[-1], params["fw"].T))
        return out
