"""Seeded weights made on the device in one jitted call, and exact
fingerprints of them.

The benchmark makes the weights itself, from ``--seed`` and the layout that
the configuration's reference gives, and hands them to the program through
``RingSession.create(params=...)``.  After the window the same call makes
them again, bit for bit, for the reference: the reference never reads a
weight that the program made or held.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.datagen import seed_sequence

_IS_SPEC = lambda x: isinstance(x, tuple) and len(x) == 4 and \
    isinstance(x[0], tuple)


def jax_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number, however large."""
    word = np.random.default_rng(seed_sequence(seed, 0)).integers(0, 2 ** 31)
    return jax.random.key(int(word))


def make_weights(layout: Dict[str, Any], seed: int,
                 sharding: Callable[[tuple], Any]) -> Dict[str, Any]:
    """``layout``: leaf -> (shape, dtype, init, std); ``sharding(path)``
    gives each leaf's placement.  One jit makes every leaf where it lives."""
    specs, treedef = jax.tree.flatten(layout, is_leaf=_IS_SPEC)
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        layout, is_leaf=_IS_SPEC)[0]]

    def build(key):
        keys = jax.random.split(key, len(specs))
        out = []
        for (shape, dtype, init, std), k in zip(specs, keys):
            if init == "zeros":
                out.append(jnp.zeros(shape, dtype))
            elif init == "ones":
                out.append(jnp.ones(shape, dtype))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32) * std)
                           .astype(dtype))
        return out

    shardings = [sharding(p) for p in paths]
    leaves = jax.jit(build, out_shardings=shardings)(jax_key(seed))
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# fingerprints: one uint32 per layer, exact and independent of layout
# ---------------------------------------------------------------------------


def _mix(x: jax.Array) -> jax.Array:
    """[rows, n] leaf -> [rows] uint32: a wrapping sum of each element's bits
    mixed with its position in the row, so any changed bit (almost surely)
    changes the row's value, and the order of the sum does not matter."""
    nbits = x.dtype.itemsize * 8
    bits = jax.lax.bitcast_convert_type(
        x, {16: jnp.uint16, 32: jnp.uint32}[nbits]).astype(jnp.uint32)
    idx = jnp.arange(x.shape[1], dtype=jnp.uint32)[None]
    h = bits * jnp.uint32(0x9E3779B1) + idx * jnp.uint32(0x85EBCA77)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> 12)
    return jnp.sum(h, axis=1, dtype=jnp.uint32)


@partial(jax.jit, static_argnums=1)
def _fingerprint(x: jax.Array, rows: int) -> jax.Array:
    return _mix(x.reshape(rows, -1))


def fingerprints(leaves: Dict[str, jax.Array], lead: Dict[str, int]
                 ) -> Dict[str, np.ndarray]:
    """Per-row fingerprints of named leaves: the first ``lead[name]`` axes
    of a leaf make its rows (its layers), the rest is one row's contents."""
    out = {k: _fingerprint(x, int(np.prod(x.shape[:lead[k]])))
           for k, x in leaves.items()}
    return {k: np.asarray(v) for k, v in out.items()}
