"""Trained LeNet/DarkNet weights for the paper's experiments.

No offline dataset ships with this container, so 'trained weights' come
from training on the procedural glyph task (repro.data.glyph_batch) - what
matters for the paper's BT statistics is the post-training weight
distribution (concentrated, near-zero-heavy), not the dataset identity.
The trained weights are committed under experiments/weights/; loading
them never retrains (``train_and_save`` is the explicit rebuild).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.data import glyph_batch
from repro.models import LeNet, DarkNetLike, init_params
from repro.optim import AdamW, cosine
from repro.train import make_train_step, init_state, checkpoint

CACHE = os.path.join(os.path.dirname(__file__), "..", "experiments", "weights")


def _train(model, channels: int, steps: int, seed: int = 0, batch: int = 64):
    params = init_params(model.specs(), jax.random.PRNGKey(seed))
    opt = AdamW(cosine(2e-3, steps, warmup=max(steps // 20, 5)),
                weight_decay=1e-4)

    def loss_fn(p, b):
        x, y = b
        return model.loss(p, x, y)

    step = jax.jit(make_train_step(loss_fn, opt))
    st = init_state(params, opt)
    hw = model.input_shape[0]
    for i in range(steps):
        st, m = step(st, glyph_batch(jax.random.PRNGKey(1000 + i), batch,
                                     hw=hw, channels=channels))
    x, y = glyph_batch(jax.random.PRNGKey(9999), 512, hw=hw, channels=channels)
    acc = float(jnp.mean(jnp.argmax(model.forward(st.params, x), -1) == y))
    return st.params, float(m["loss"]), acc


def _model(name: str):
    return LeNet() if name == "lenet" else DarkNetLike()


def get_trained(name: str):
    """name in {lenet, darknet}; returns (model, trained params, accuracy)
    restored from the committed checkpoint under ``experiments/weights``.

    Raises FileNotFoundError when the checkpoint is missing or does not
    restore; it never retrains. :func:`train_and_save` rebuilds it.
    """
    model = _model(name)
    ckpt_dir = os.path.join(CACHE, name)
    ref = init_params(model.specs(), jax.random.PRNGKey(0))
    got = checkpoint.restore(ckpt_dir, {"params": ref, "acc": jnp.zeros(())})
    if got is None:
        raise FileNotFoundError(
            f"no restorable {name} checkpoint under {os.path.abspath(ckpt_dir)}"
            f"; rebuild it with benchmarks._trained.train_and_save({name!r})")
    blob = got[1]
    return model, blob["params"], float(blob["acc"])


def train_and_save(name: str, steps: int = 400):
    """Train ``name`` on the glyph task and overwrite its checkpoint;
    returns (model, params, accuracy)."""
    model = _model(name)
    params, _, acc = _train(model, model.input_shape[-1], steps)
    checkpoint.save(os.path.join(CACHE, name), steps,
                    {"params": params, "acc": jnp.asarray(acc)})
    return model, params, acc


def random_params(name: str, seed: int = 1):
    model = _model(name)
    return model, init_params(model.specs(), jax.random.PRNGKey(seed))
