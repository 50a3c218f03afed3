"""The DeepSeek-V2 architecture, for configuration files with
``"arch": "deepseek_v2"``: a decoder whose every layer is multi-head
latent attention (no query compression), the first
``first_k_dense_replace`` layers with a dense SwiGLU MLP, every later
layer with ``n_routed_experts`` routed SwiGLU experts and
``n_shared_experts`` shared ones (run as one MLP).

It answers what ``archs/mixtral.py`` answers (see there): the tree's
layout and leaves, the program's configuration, the (layer, expert)
pairs, the model's operations and the engine's device parts. The stacked
axes are "D" (the dense layers), "L" (the expert layers) and "E" (the
routed experts). The program is imported only inside ``program_config``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from benchlib import weights as W


def _widths(c: dict):
    h = c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    return h, nope, rope, c["v_head_dim"], c["kv_lora_rank"]


def _attn(prefix: str, c: dict, axes: str) -> W.Layout:
    d = c["hidden_size"]
    h, nope, rope, vd, r = _widths(c)
    return [
        (f"{prefix}/attn/wq", (d, h * (nope + rope)), axes),
        (f"{prefix}/attn/wkv_a", (d, r + rope), axes),
        (f"{prefix}/attn/kv_norm/scale", (r,), axes),
        (f"{prefix}/attn/wkv_b", (r, h * (nope + vd)), axes),
        (f"{prefix}/attn/wo", (h * vd, d), axes),
        (f"{prefix}/attn_norm/scale", (d,), axes),
    ]


def layout(c: dict) -> W.Layout:
    d, v = c["hidden_size"], W.served(c, "vocab_rows")
    f, e = c["moe_intermediate_size"], c["n_routed_experts"]
    fs = f * c["n_shared_experts"]
    dense = c["intermediate_size"]
    return [
        ("embed/table", (v, d), ""),
        ("final_norm/scale", (d,), ""),
        ("lm_head/table", (v, d), ""),
        *_attn("layers", c, "L"),
        ("layers/moe/router", (d, e), "L"),
        ("layers/moe/w_gate", (d, f), "LE"),
        ("layers/moe/w_up", (d, f), "LE"),
        ("layers/moe/w_down", (f, d), "LE"),
        ("layers/ffn_norm/scale", (d,), "L"),
        ("layers/shared_mlp/w_gate", (d, fs), "L"),
        ("layers/shared_mlp/w_up", (d, fs), "L"),
        ("layers/shared_mlp/w_down", (fs, d), "L"),
        *_attn("dense_layers", c, "D"),
        ("dense_layers/mlp/w_gate", (d, dense), "D"),
        ("dense_layers/mlp/w_up", (d, dense), "D"),
        ("dense_layers/mlp/w_down", (dense, d), "D"),
        ("dense_layers/ffn_norm/scale", (d,), "D"),
    ]


def moe_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def _counts(c: dict) -> Dict[str, int]:
    return {"L": moe_layers(c), "D": c["first_k_dense_replace"],
            "E": c["n_routed_experts"]}


def full_shapes(c: dict) -> Dict[str, tuple]:
    return W.full_shapes(layout(c), _counts(c))


def make_params(c: dict, seed: int) -> dict:
    """The whole tree on the default device, in one jitted call."""
    return W.make_tree(layout(c), _counts(c), seed)


def leaf_slice(c: dict, seed: int, name: str, layer: int = 0,
               expert: int = 0):
    """One slice of leaf ``name`` (``layer`` counts within the leaf's
    own stack), equal to the same slice of :func:`make_params`' tree."""
    return W.tree_slice(layout(c), seed, name, layer, expert)


def program_config(c: dict):
    """The program's ModelConfig for configuration file ``c``."""
    from repro.configs import YarnConfig, get_config
    base = get_config(c["program_arch"])
    h, nope, rope, vd, r = _widths(c)
    y = c["rope_scaling"]
    plan = c["plan"]
    return base.replace(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        first_dense_layers=c["first_k_dense_replace"],
        embed_multiplier=float(W.served(c, "embedding_multiplier")),
        attention=dataclasses.replace(
            base.attention, num_heads=h,
            num_kv_heads=c["num_key_value_heads"], head_dim=nope,
            qk_rope_head_dim=rope, v_head_dim=vd, kv_lora_rank=r,
            rope_theta=float(c["rope_theta"]),
            yarn=YarnConfig(
                factor=float(y["factor"]),
                original_max_position_embeddings=int(
                    y["original_max_position_embeddings"]),
                beta_fast=float(y["beta_fast"]),
                beta_slow=float(y["beta_slow"]),
                mscale=float(y["mscale"]),
                mscale_all_dim=float(y["mscale_all_dim"]))),
        moe=dataclasses.replace(
            base.moe, num_experts=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"],
            d_ff_expert=c["moe_intermediate_size"],
            d_ff_shared=c["moe_intermediate_size"] * c["n_shared_experts"],
            norm_topk_prob=bool(c["norm_topk_prob"]),
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            capacity_factor=c["moe_capacity_factor"]),
        mop=dataclasses.replace(base.mop, ladder=tuple(plan["ladder"]),
                                group_size=plan["group_size"]))


def expert_pairs(c: dict) -> int:
    return moe_layers(c) * c["n_routed_experts"]


def device_parts(engine) -> dict:
    """The serve tree's expert banks and the latent paged KV pool."""
    return {"banks": engine.serve_params["layers"]["moe"]["banks"],
            "kv_pool": engine.kv_pool}


# -- model operations ---------------------------------------------------------

def _mlp_flops(c: dict, dense: bool) -> float:
    """One token through a layer's MLP part: the dense MLP, or the
    router, ``num_experts_per_tok`` routed experts and the shared MLP."""
    d = c["hidden_size"]
    if dense:
        return 2.0 * 3 * d * c["intermediate_size"]
    f = c["moe_intermediate_size"]
    return (2.0 * d * c["n_routed_experts"]
            + c["num_experts_per_tok"] * 2 * 3 * d * f
            + 2 * 3 * d * f * c["n_shared_experts"])


def _proj_flops(c: dict) -> float:
    """One token's query, latent-and-rotary-key and output projections."""
    d = c["hidden_size"]
    h, nope, rope, vd, r = _widths(c)
    return 2.0 * d * (h * (nope + rope) + r + rope) + 2.0 * h * vd * d


def _layers(c: dict):
    k = c["first_k_dense_replace"]
    return [(k, True), (moe_layers(c), False)]


def unembed_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def prefill_flops(c: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens, attention expanded: every
    token's latent up-projected to each head's key and value, and every
    position attends to itself and the positions before it; logits for
    the last one only."""
    s = int(prompt_len)
    h, nope, rope, vd, r = _widths(c)
    up = 2.0 * r * h * (nope + vd)
    attn = 2.0 * h * (nope + rope + vd) * s * (s + 1) / 2
    return sum(n * (s * (_proj_flops(c) + up + _mlp_flops(c, dense))
                    + attn) for n, dense in _layers(c)) + unembed_flops(c)


def decode_flops(c: dict, context: int) -> float:
    """One decoded token that attends to ``context`` positions (itself
    included), attention absorbed: the query folded into the latent
    space, scores against the latent and rotary key, the attended
    latent up-projected to each head's value; with its logits."""
    h, nope, rope, vd, r = _widths(c)
    absorb = 2.0 * h * nope * r + 2.0 * h * r * vd
    attn = 2.0 * h * (r + rope + r) * context
    return sum(n * (_proj_flops(c) + absorb + attn + _mlp_flops(c, dense))
               for n, dense in _layers(c)) + unembed_flops(c)
