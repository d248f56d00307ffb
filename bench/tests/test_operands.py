"""The benchmark's own operand traffic: every (inputs, weights) row pair
multiplies out to its neuron's pre-activation, and the configurations'
networks give the traffic the cells state."""
import json
import os

import numpy as np
import pytest

from conftest import ROOT

import operands


def _conv(x, w):
    import jax
    import jax.numpy as jnp
    y = jax.lax.conv_general_dilated(
        jnp.asarray(x)[None], jnp.asarray(w), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    return np.asarray(y)


def test_rows_multiply_out_to_the_layer():
    rng = np.random.default_rng(0)
    params = {"aw": rng.normal(size=(3, 3, 3, 4)).astype(np.float32),
              "ab": rng.normal(size=4).astype(np.float32),
              "bw": rng.normal(size=(3, 3, 4, 5)).astype(np.float32),
              "bb": rng.normal(size=5).astype(np.float32),
              "cw": rng.normal(size=(45, 6)).astype(np.float32),
              "cb": rng.normal(size=6).astype(np.float32)}
    config = {"layers": [
        {"op": "conv", "weight": "aw", "bias": "ab", "act": "leaky0.1",
         "pool": 2},
        {"op": "conv", "weight": "bw", "bias": "bb", "act": "tanh"},
        {"op": "dense", "weight": "cw", "bias": "cb", "act": None}]}
    img = rng.uniform(size=(12, 12, 3)).astype(np.float32)
    layers = operands.layer_operands(config, params, img)
    assert [i.shape for i, _ in layers] == [(100 * 4, 27), (9 * 5, 36),
                                            (6, 45)]
    x = img
    for (inp, wgt), spec in zip(layers[:2], config["layers"]):
        pre = _conv(x, params[spec["weight"]]) + params[spec["bias"]]
        # neurons: channels outermost, positions row-major
        want = pre.transpose(2, 0, 1).reshape(-1)
        got = (inp * wgt).sum(1) + np.repeat(params[spec["bias"]],
                                             inp.shape[0] // pre.shape[-1])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        y = operands._act(pre, spec["act"])
        x = operands._pool(y, spec["pool"]) if spec.get("pool") else y
    inp, wgt = layers[2]
    np.testing.assert_allclose(inp[0], x.reshape(-1), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(wgt, params["cw"].T)


@pytest.mark.parametrize("name,packets,flat", [
    ("lenet_8x8_mc8", [4704, 1600, 120, 84, 10], [25, 150, 400, 120, 84]),
    ("darknet_8x8_mc8", [61504, 26912, 9216, 2048, 10],
     [27, 144, 288, 576, 512])])
def test_configuration_traffic(name, packets, flat):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        config = json.load(f)
    layers = operands.cell_operands(config, 2**31 + 12345)
    assert [i.shape for i, _ in layers] == list(zip(packets, flat))
    assert all(i.dtype == w.dtype == np.float32 for i, w in layers)
    again = operands.cell_operands(config, 2**31 + 12345)
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(layers, again))
    other = operands.cell_operands(config, 7)
    assert not np.array_equal(layers[0][0], other[0][0])
