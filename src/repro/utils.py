"""Shared small utilities: pytree helpers, dtype handling, rng splitting."""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# the checkout root: src/repro/utils.py -> ../..
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this before their first compile.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    is overridden; otherwise the cache is the fixed ``.jax_cache/`` at the
    checkout root (a fixed path, so a later run finds the entries).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def tree_size(tree: PyTree) -> int:
    """Total number of scalar elements in a pytree of arrays."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    """Total bytes of a pytree of arrays (uses each leaf's dtype)."""
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def tree_zeros_like(tree: PyTree, dtype=None) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree
    )


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_scale(tree: PyTree, s) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x * s, tree)


def tree_finite(tree: PyTree) -> jax.Array:
    """Scalar bool: every element of every leaf is finite."""
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(tree)]
    return functools.reduce(jnp.logical_and, leaves, jnp.asarray(True))


def key_iter(seed: int) -> Iterator[jax.Array]:
    """Infinite deterministic stream of PRNG keys."""
    key = jax.random.PRNGKey(seed)
    while True:
        key, sub = jax.random.split(key)
        yield sub


def global_norm(tree: PyTree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def named_tree_map(fn: Callable, tree: PyTree, *rest: PyTree) -> PyTree:
    """tree_map that also passes the key-path string as first argument."""

    def _fn(path, leaf, *others):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        return fn(name, leaf, *others)

    return jax.tree_util.tree_map_with_path(_fn, tree, *rest)
