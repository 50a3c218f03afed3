"""Random weights from ``--seed``, made by the benchmark and not by the
program, so that the reference can make the same ones again.

An architecture file (``bench/archs/<arch>.py``) states its tree as a
layout: for every leaf its name, the shape of one slice and the axes
stacked in front of it, each axis a letter the architecture counts
(``"L"`` its layers, ``"E"`` its experts, ...). From a layout this module
makes the tree, its shapes and any one slice. Every leaf, layer and
expert has a key of its own (``slice_key``), so one slice can be
regenerated alone with the values the whole tree holds. Leaves are
bfloat16, the type they are served in; norm scales are ones; every
other leaf is normal with standard deviation ``1 / sqrt(fan_in)``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

#: (name, shape of one slice, stacked axes) for every leaf of a tree
Layout = List[Tuple[str, tuple, str]]


def served(c: dict, key: str):
    """What the program serves with: the configuration's stated
    departure from the published model (``program_departures``) where it
    has one, else the published value."""
    published = {"embedding_multiplier": 1.0, "vocab_rows": c["vocab_size"]}
    dep = c.get("program_departures", {})
    return dep[key] if key in dep else c.get(key, published.get(key))


def full_shapes(layout: Layout, counts: Dict[str, int]) -> Dict[str, tuple]:
    """Every leaf's whole shape, its stacked axes counted by ``counts``."""
    return {name: tuple(counts[a] for a in axes) + shape
            for name, shape, axes in layout}


def base_key(seed: int) -> jax.Array:
    """A key from any whole seed, also one past 32 bits."""
    k = jax.random.key(0)
    k = jax.random.fold_in(k, int(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(k, (int(seed) >> 32) & 0xFFFFFFFF)


def slice_key(seed_key, leaf: int, layer: int = 0, expert: int = 0):
    k = jax.random.fold_in(seed_key, leaf)
    return jax.random.fold_in(jax.random.fold_in(k, layer), expert)


def _draw(key, name: str, shape) -> jax.Array:
    if name.endswith("/scale"):
        return jnp.ones(shape, jnp.bfloat16)
    std = 1.0 / (shape[0] ** 0.5)
    return (jax.random.normal(key, shape, jnp.float32) * std
            ).astype(jnp.bfloat16)


def make_tree(layout: Layout, counts: Dict[str, int], seed: int) -> dict:
    """The whole tree on the default device, in one jitted call. A leaf
    stacks at most two axes; the first is a slice key's ``layer``, the
    second its ``expert``."""
    def build(seed_key):
        flat = {}
        for i, (name, shape, axes) in enumerate(layout):
            one = lambda l, e: _draw(slice_key(seed_key, i, l, e), name,
                                     shape)
            if axes == "":
                flat[name] = one(0, 0)
            elif len(axes) == 1:
                flat[name] = jax.vmap(lambda l: one(l, 0))(
                    jnp.arange(counts[axes]))
            else:
                flat[name] = jax.vmap(lambda l: jax.vmap(
                    lambda e: one(l, e))(jnp.arange(counts[axes[1]])))(
                        jnp.arange(counts[axes[0]]))
        return _nest(flat)

    return jax.jit(build)(base_key(seed))


def tree_slice(layout: Layout, seed: int, name: str, layer: int = 0,
               expert: int = 0) -> jax.Array:
    """One layer's (and expert's) slice of leaf ``name``, equal to the
    same slice of :func:`make_tree`'s tree."""
    for i, (nm, shape, _) in enumerate(layout):
        if nm == name:
            return _slice_fn(name, shape)(slice_key(base_key(seed), i,
                                                    layer, expert))
    raise KeyError(name)


_SLICE_FNS: dict = {}


def _slice_fn(name, shape):
    k = (name, shape)
    if k not in _SLICE_FNS:
        _SLICE_FNS[k] = jax.jit(lambda key: _draw(key, name, shape))
    return _SLICE_FNS[k]


def _nest(flat: dict) -> dict:
    out: dict = {}
    for name, v in flat.items():
        node = out
        *parts, last = name.split("/")
        for p in parts:
            node = node.setdefault(p, {})
        node[last] = v
    return out
