"""Seeded weights of a quantized transformer, by leaf name and layer.

Both sides of the comparison draw from here: ``model.py`` packs the codes
into the program's layout, and the plain reference (``references/``)
dequantizes them itself.  So nothing the program makes ever reaches the
reference, and yet both compute with the same numbers.

Every value is a pure function of ``(seed, leaf name, layer)``: a leaf of
one layer can be drawn without the rest of the model, which is what lets
the float32 reference run layer by layer on a chip that cannot hold the
float model.  This module imports nothing from the program.

Spreads follow the program's own initialisation: a linear's dequantized
weight ``s·(q − z)`` has the init's standard deviation ``in^-0.5`` (codes
uniform over ``[0, 2^bits)``, zero near the middle) and the head is normal
with that deviation.  The embedding is standard normal, not the init's
0.02: at 0.02 the 32 blocks' outputs drown each token's own embedding, the
final hidden state hardly depends on the tokens, and every position puts
the same token first by the same margin, so a comparison of served tokens
would see one margin per sequence instead of one per token.
Norm gains, norm biases and linear biases carry small random offsets so
that a dropped or misplaced one shows in the logits.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

_M31 = 0x7FFFFFFF


def root_key(seed: int):
    """PRNG key of a seed of any size up to 62 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & _M31),
                              (seed >> 31) & _M31)


def _key(root, name: str, layer):
    return jax.random.fold_in(
        jax.random.fold_in(root, zlib.crc32(name.encode()) & _M31), layer)


def code_std(bits: int) -> float:
    """Standard deviation of a code uniform over ``[0, 2^bits)``."""
    return float(np.sqrt(((1 << bits) ** 2 - 1) / 12.0))


def codes(root, name: str, layer, n: int, k: int, bits: int):
    """(n, k) int32 codes of linear ``name`` (e.g. ``layers/attn/wq``)."""
    return jax.random.randint(_key(root, name + "/codes", layer), (n, k), 0,
                              1 << bits, dtype=jnp.int32)


def scale(root, name: str, layer, n: int, groups: int, k: int, bits: int):
    base = k ** -0.5 / code_std(bits)
    e = jax.random.normal(_key(root, name + "/scale", layer), (n, groups))
    return (base * jnp.exp(0.1 * e)).astype(jnp.float32)


def zero(root, name: str, layer, n: int, groups: int, bits: int):
    e = jax.random.normal(_key(root, name + "/zero", layer), (n, groups))
    return (((1 << bits) - 1) / 2.0 + 0.25 * e).astype(jnp.float32)


def bias(root, name: str, layer, n: int):
    return 0.02 * jax.random.normal(_key(root, name + "/b", layer), (n,))


def norm_gain(root, name: str, layer, d: int):
    return 1.0 + 0.02 * jax.random.normal(_key(root, name + "/g", layer), (d,))


def norm_bias(root, name: str, layer, d: int):
    return 0.02 * jax.random.normal(_key(root, name + "/b", layer), (d,))


def embedding(root, vocab: int, d: int):
    return jax.random.normal(_key(root, "embed/emb", 0), (vocab, d))


def head(root, vocab: int, d: int):
    return d ** -0.5 * jax.random.normal(_key(root, "lm_head/w", 0),
                                         (vocab, d))


def task_multiplier(seed: int, task: int, name: str, shape) -> np.ndarray:
    """Host float32 factor that turns the base scales of ``name`` (the whole
    layer-stacked leaf) into task ``task``'s: ``1 + 0.05·N(0, 1)``.  Task
    scale sets are what PEQA tunes and serves, so they differ from each
    other by element, not by a common factor."""
    rng = np.random.default_rng(
        [seed & _M31, seed >> 31, task, zlib.crc32(name.encode())])
    return (1.0 + 0.05 * rng.standard_normal(shape, np.float32)).astype(
        np.float32)
