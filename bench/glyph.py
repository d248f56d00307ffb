"""The cell's input image, made from ``--seed`` on the host CPU.

A copy of the repository's procedural glyph generator (a 7-segment digit
upsampled, jittered, scaled and noised), kept here so that the benchmark's
inputs cannot move with the program. It runs on the CPU device: a TPU's
normal sampler differs from the CPU's in the last bits, which would move
the fixed-point traffic built on the image.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_SEGS = ("111101101101111", "010010010010010", "111001111100111",
         "111001111001111", "101101111001001", "111100111001111",
         "111100111101111", "111001001001001", "111101111101111",
         "111101111001111")
GLYPHS = np.stack([np.array([int(c) for c in s], np.float32).reshape(5, 3)
                   for s in _SEGS])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any seed up to 64 bits, beyond int32 included."""
    seed = int(seed) % (1 << 64)
    return jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)


def glyph_image(seed: int, hw: int, channels: int) -> np.ndarray:
    """One (hw, hw, channels) float32 image in [0, 1]."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        key = jax.device_put(seed_key(seed), cpu)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        label = jax.random.randint(k1, (1,), 0, 10)
        img = jnp.asarray(GLYPHS)[label]                        # (1, 5, 3)
        up = hw // 8
        img = jax.image.resize(img, (1, 5 * up, 3 * up), "nearest")
        ph, pw = hw - 5 * up, hw - 3 * up
        img = jnp.pad(img, ((0, 0), (ph // 2, ph - ph // 2),
                            (pw // 2, pw - pw // 2)))
        sh = jax.random.randint(k2, (1, 2), -2, 3)
        img = jnp.roll(img[0], (sh[0, 0], sh[0, 1]), axis=(0, 1))[None]
        img = img * jax.random.uniform(k3, (1, 1, 1), minval=0.7, maxval=1.0)
        img = img + 0.15 * jax.random.normal(k4, img.shape)
        img = jnp.clip(img, 0.0, 1.0)[..., None]
        if channels > 1:
            img = jnp.repeat(img, channels, axis=-1)
        return np.asarray(img[0], np.float32)
