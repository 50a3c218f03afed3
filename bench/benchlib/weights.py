"""Random weights from ``--seed``, made by the benchmark and not by the
program, so that the reference can make the same ones again.

Every leaf, layer and expert has a key of its own (``slice_key``), so
one layer or one expert can be regenerated alone (``leaf_slice``) with
the values the whole tree holds. The tree has the train layout the
engine takes (``layers/moe/w_gate`` is ``(L, E, d, f)``, ...), in
bfloat16, the type it is served in; norm scales are ones; every other
leaf is normal with standard deviation ``1 / sqrt(fan_in)``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

def served(c: dict, key: str):
    """What the program serves with: the configuration's stated
    departure from the published model (``program_departures``) where it
    has one, else the published value."""
    published = {"embedding_multiplier": 1.0, "vocab_rows": c["vocab_size"]}
    dep = c.get("program_departures", {})
    return dep[key] if key in dep else c.get(key, published.get(key))


def layout(c: dict) -> List[Tuple[str, tuple, str]]:
    """(name, shape of one slice, axes stacked in front of it) for every
    leaf: "L" stacks the layers, "LE" the layers and then the experts."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    e, v = c["num_local_experts"], served(c, "vocab_rows")
    return [
        ("embed/table", (v, d), ""),
        ("final_norm/scale", (d,), ""),
        ("lm_head/table", (v, d), ""),
        ("layers/attn/wq", (d, h * hd), "L"),
        ("layers/attn/wk", (d, hkv * hd), "L"),
        ("layers/attn/wv", (d, hkv * hd), "L"),
        ("layers/attn/wo", (h * hd, d), "L"),
        ("layers/attn_norm/scale", (d,), "L"),
        ("layers/moe/router", (d, e), "L"),
        ("layers/moe/w_gate", (d, f), "LE"),
        ("layers/moe/w_up", (d, f), "LE"),
        ("layers/moe/w_down", (f, d), "LE"),
        ("layers/ffn_norm/scale", (d,), "L"),
    ]


def full_shapes(c: dict) -> Dict[str, tuple]:
    n = {"L": c["num_hidden_layers"], "E": c["num_local_experts"]}
    return {name: tuple(n[a] for a in axes) + shape
            for name, shape, axes in layout(c)}


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


def make_params(c: dict, seed: int) -> dict:
    """The whole tree on the default device, in one jitted call."""
    nl, ne = c["num_hidden_layers"], c["num_local_experts"]

    def build(seed_key):
        flat = {}
        for i, (name, shape, axes) in enumerate(layout(c)):
            one = lambda l, e: _draw(slice_key(seed_key, i, l, e), name,
                                     shape)
            if axes == "":
                flat[name] = one(0, 0)
            elif axes == "L":
                flat[name] = jax.vmap(lambda l: one(l, 0))(jnp.arange(nl))
            else:
                flat[name] = jax.vmap(lambda l: jax.vmap(
                    lambda e: one(l, e))(jnp.arange(ne)))(jnp.arange(nl))
        return _nest(flat)

    return jax.jit(build)(base_key(seed))


def leaf_slice(c: dict, seed: int, name: str, layer: int = 0,
               expert: int = 0) -> jax.Array:
    """One layer's (and expert's) slice of leaf ``name``, equal to the
    same slice of :func:`make_params`' tree."""
    for i, (nm, shape, axes) in enumerate(layout(c)):
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
