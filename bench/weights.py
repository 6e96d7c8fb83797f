"""Seeded weights and inputs, made the same way for the program and for
the plain references.

Every tensor is ``randint(-127, 127) * 2**-k``: a uniform distribution on a
grid whose values are exact in bfloat16 and float32 and whose generation
uses integer arithmetic only. So a tensor made in one big jitted call (the
program's parameters) and the same tensor made alone (a reference, one
layer or one expert at a time) are equal bit for bit, on any backend. The
standard deviation is ``127.5 / sqrt(3) * 2**-k``, with ``k`` the power of
two nearest the requested one.

A tensor is named by a path of strings and integers (leaf, layer, expert);
its key is the seed's base key folded with each part.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

_UNIFORM_STD = 127.5 / math.sqrt(3.0)


def seed_key(seed: int) -> jax.Array:
    """The base key of ``seed`` (any whole number, folded in as two 32-bit
    halves so large seeds stay distinct). Pass it into jitted code as an
    argument: a seed baked in as a constant would make every seed a new
    program to compile."""
    key = jax.random.key(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF):
        key = jax.random.fold_in(key, np.uint32(word))
    return key


def key_for(key: jax.Array, *path) -> jax.Array:
    """The key of the tensor at ``path`` under the base ``key``."""
    for part in path:
        word = part if isinstance(part, int) else zlib.crc32(part.encode())
        key = jax.random.fold_in(key, np.uint32(word))
    return key


def grid_step(std: float) -> float:
    """The grid's step 2**-k for a tensor of standard deviation ``std``."""
    return 2.0 ** round(math.log2(std / _UNIFORM_STD))


def tensor(key: jax.Array, path: tuple, shape, std: float, dtype) -> jax.Array:
    ints = jax.random.randint(key_for(key, *path), shape, -127, 128, jnp.int32)
    return ints.astype(dtype) * jnp.asarray(grid_step(std), dtype)

