"""The Mixtral architecture, for configuration files with
``"arch": "mixtral"``: a decoder whose every layer is grouped-query
attention with rotary embeddings and a sparse mixture of SwiGLU experts.

An architecture file answers, for a configuration file ``c``, what the
harness cannot know without the architecture:

- ``layout(c)`` and ``full_shapes(c)``: the train-layout tree the
  benchmark makes, which must be the program's ``param_shapes()``;
- ``make_params(c, seed)``: that tree from the seed; ``leaf_slice``: one
  slice of it, for the reference;
- ``program_config(c)``: the program's ``ModelConfig``;
- ``expert_pairs(c)``: how many (layer, expert) pairs hold an expert;
- ``prefill_flops(c, n)`` and ``decode_flops(c, context)``: the model's
  operations for a prompt and for one decoded token;
- ``device_parts(engine)``: the parts of the engine's device state that
  set-up reckons, beside the train copy.

The program is imported only inside ``program_config``, so that the
reference can import this file and nothing of the program.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from benchlib import weights as W
from benchlib.weights import served


def layout(c: dict) -> W.Layout:
    """Every leaf: "L" stacks the layers, "LE" the layers and then the
    experts."""
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


def _counts(c: dict) -> Dict[str, int]:
    return {"L": c["num_hidden_layers"], "E": c["num_local_experts"]}


def full_shapes(c: dict) -> Dict[str, tuple]:
    return W.full_shapes(layout(c), _counts(c))


def make_params(c: dict, seed: int) -> dict:
    """The whole tree on the default device, in one jitted call."""
    return W.make_tree(layout(c), _counts(c), seed)


def leaf_slice(c: dict, seed: int, name: str, layer: int = 0,
               expert: int = 0):
    """One layer's (and expert's) slice of leaf ``name``, equal to the
    same slice of :func:`make_params`' tree."""
    return W.tree_slice(layout(c), seed, name, layer, expert)


def program_config(c: dict):
    """The program's ModelConfig for configuration file ``c``."""
    from repro.configs import get_config
    base = get_config(c["program_arch"])
    plan = c["plan"]
    return base.replace(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        attention=dataclasses.replace(
            base.attention, num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], sliding_window=c["sliding_window"],
            rope_theta=c["rope_theta"]),
        moe=dataclasses.replace(
            base.moe, num_experts=c["num_local_experts"],
            top_k=c["num_experts_per_tok"],
            d_ff_expert=c["intermediate_size"],
            capacity_factor=c["moe_capacity_factor"]),
        mop=dataclasses.replace(base.mop, ladder=tuple(plan["ladder"]),
                                group_size=plan["group_size"]))


def expert_pairs(c: dict) -> int:
    return c["num_hidden_layers"] * c["num_local_experts"]


def device_parts(engine) -> dict:
    """The serve tree's expert banks and the paged KV pool."""
    return {"banks": engine.serve_params["layers"]["moe"]["banks"],
            "kv_pool": engine.kv_pool}


# -- model operations ---------------------------------------------------------

def layer_flops(c: dict, context: int) -> float:
    """One token through one decoder layer at ``context`` attended keys:
    q/k/v/o projections, scores and values, router, and the
    ``num_experts_per_tok`` experts it is routed to."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    proj = 2 * d * (h * hd + 2 * hkv * hd) + 2 * h * hd * d
    attn = 4 * h * hd * context
    router = 2 * d * c["num_local_experts"]
    experts = c["num_experts_per_tok"] * 2 * 3 * d * f
    return float(proj + attn + router + experts)


def unembed_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def prefill_flops(c: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens: every position attends to
    itself and the positions before it; logits for the last one only."""
    s = int(prompt_len)
    per_layer = s * layer_flops(c, 0) + 4 * c["num_attention_heads"] \
        * c["head_dim"] * s * (s + 1) / 2
    return c["num_hidden_layers"] * per_layer + unembed_flops(c)


def decode_flops(c: dict, context: int) -> float:
    """One decoded token that attends to ``context`` positions (itself
    included), with its logits."""
    return c["num_hidden_layers"] * layer_flops(c, context) \
        + unembed_flops(c)
