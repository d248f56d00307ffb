"""Deterministic, shard-addressable synthetic data pipelines.

Every batch is a pure function of (stream seed, step, shard index) via
threefry counters - the property the fault-tolerance story relies on: any
host can regenerate any other host's shard after an elastic re-mesh or a
straggler eviction, with no data-service round trip (DESIGN.md Sec. 6).

Token streams are Zipf-distributed (text-like marginal statistics matter
for the paper's BT analyses - uniform random tokens would understate bit
correlations). Glyph images give LeNet/DarkNet a trainable task without any
offline dataset: procedural digit-like strokes with noise and affine jitter.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TokenStream", "glyph_batch", "GLYPHS"]


@dataclasses.dataclass(frozen=True)
class TokenStream:
    """Zipf-ish LM token stream with next-token targets."""

    vocab: int
    seq_len: int
    global_batch: int
    seed: int = 0
    zipf_a: float = 1.2

    def batch(self, step: int, shard: int = 0, num_shards: int = 1):
        """Returns (tokens, targets, mask) for one shard of one step.

        The global batch is a pure function of (seed, step); a shard is a
        row slice of it. This makes shard assignments independent of the
        shard COUNT - the invariant elastic re-meshing needs (a job that
        shrinks from 32 to 16 data shards re-covers the identical global
        batch), and any host can regenerate any other host's shard.
        """
        if self.global_batch % num_shards:
            raise ValueError("global_batch must divide by num_shards")
        b = self.global_batch // num_shards
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
        # inverse-CDF Zipf over the vocab (vectorized, no rejection)
        u = jax.random.uniform(key, (self.global_batch, self.seq_len + 1),
                               minval=1e-6)
        ranks = jnp.exp(jnp.log(u) * (-1.0 / (self.zipf_a - 1.0)))
        toks = jnp.clip(ranks.astype(jnp.int32) - 1, 0, self.vocab - 1)
        toks = toks[shard * b:(shard + 1) * b]
        tokens, targets = toks[:, :-1], toks[:, 1:]
        mask = jnp.ones_like(targets, jnp.float32)
        return tokens, targets, mask


# 7-segment-style glyph templates for the 10 classes (rows of 5x3 cells).
_SEGS = {
    0: "111101101101111", 1: "010010010010010", 2: "111001111100111",
    3: "111001111001111", 4: "101101111001001", 5: "111100111001111",
    6: "111100111101111", 7: "111001001001001", 8: "111101111101111",
    9: "111101111001111",
}
GLYPHS = np.stack([
    np.array([int(c) for c in _SEGS[d]], np.float32).reshape(5, 3)
    for d in range(10)])


def glyph_batch(key: jax.Array, batch: int, hw: int = 32, channels: int = 1):
    """Procedural digit-like images: upsampled glyph + jitter + noise.

    Returns (images (B, hw, hw, channels) float32 in [0, 1], labels (B,))
    on the default device. They are made on the host CPU, so one key gives
    the same bits on every backend: a TPU's normal sampler differs from the
    CPU's in the last bits, which moves the fixed-8 traffic built on them.
    """
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        img, labels = _glyphs(jax.device_put(key, cpu), batch, hw, channels)
    return jnp.asarray(np.asarray(img)), jnp.asarray(np.asarray(labels))


def _glyphs(key: jax.Array, batch: int, hw: int, channels: int):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    labels = jax.random.randint(k1, (batch,), 0, 10)
    glyphs = jnp.asarray(GLYPHS)[labels]                     # (B, 5, 3)
    up = hw // 8
    img = jax.image.resize(glyphs, (batch, 5 * up, 3 * up), "nearest")
    ph = hw - 5 * up
    pw = hw - 3 * up
    img = jnp.pad(img, ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2)))
    # jitter: random shift by up to +-2 px via roll
    sh = jax.random.randint(k2, (batch, 2), -2, 3)
    def roll_one(im, s):
        return jnp.roll(im, (s[0], s[1]), axis=(0, 1))
    img = jax.vmap(roll_one)(img, sh)
    img = img * jax.random.uniform(k3, (batch, 1, 1), minval=0.7, maxval=1.0)
    img = img + 0.15 * jax.random.normal(k4, img.shape)
    img = jnp.clip(img, 0.0, 1.0)[..., None]
    if channels > 1:
        img = jnp.repeat(img, channels, axis=-1)
    return img.astype(jnp.float32), labels
