"""Decoder stacks: dense/MoE transformer, RWKV6, and the Zamba2 hybrid.

All stacks scan over stacked layer params (O(1) HLO in depth — DESIGN.md §4)
and share the same cache protocol:

    forward(params, x, positions, caches=None) -> (y, new_caches, aux)

``caches=None``  -> full-sequence (train / no-cache prefill)
``caches`` given -> cached attention (prefill writes, decode S==1 reads)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import mixed_moe
from repro.models import layers as L
from repro.models import ssm as S


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return fn


def _scan_or_loop(body, x, xs, cfg: ModelConfig):
    """lax.scan over stacked layer params, or a python loop (hillclimb knob:
    unrolled HLO lets XLA overlap across layer boundaries)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, x, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x, y = body(x, jax.tree_util.tree_map(lambda a: a[i], xs))
        ys.append(y)
    stack = None if ys[0] is None else jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *ys)
    return x, stack


# ---------------------------------------------------------------------------
# Dense / MoE transformer
# ---------------------------------------------------------------------------

def _ffn_or_moe(p, xn, cfg: ModelConfig, par, train, use_kernel, aux_acc,
                token_valid=None, moe_capacity=None):
    """Returns (y, aux_acc, route_ids|None) — ids are the (T, k) routed
    expert slots in BANK order (serve layout permutes experts q4-first).

    ``token_valid`` (B, S) bool masks idle decode slots / prefill pads out
    of the dispatch: their ids are remapped to the out-of-range sentinel
    ``num_experts`` (dropped by ``_local_slot``) so they never occupy
    expert capacity and displace real tokens."""
    if cfg.moe is None:
        return L.mlp(p["mlp"], xn, cfg.act), aux_acc, None
    b, s, d = xn.shape
    x2 = xn.reshape(b * s, d)
    weights, ids, aux = mixed_moe.route(p["moe"]["router"], x2, cfg.moe,
                                        train=train)
    if token_valid is not None:
        v = token_valid.reshape(b * s)
        ids = jnp.where(v[:, None], ids, cfg.moe.num_experts)
        weights = jnp.where(v[:, None], weights, 0.0)
    banks = p["moe"].get("banks")
    if banks is None:
        banks = mixed_moe.train_banks(p["moe"])
    y = mixed_moe.moe_apply(banks, x2, weights, ids, cfg.moe, par,
                            act=cfg.act, use_kernel=use_kernel,
                            capacity=moe_capacity).reshape(b, s, d)
    if "shared_mlp" in p:
        # shared experts: every token, bf16, off the precision ladder
        y = y + L.mlp(p["shared_mlp"], xn, cfg.act)
    for k, v in aux.items():
        aux_acc[k] = aux_acc.get(k, 0.0) + v
    return y, aux_acc, ids


def decoder_forward(params, cfg: ModelConfig, x, positions, *,
                    caches=None, par=None, train=False, use_kernel=False,
                    enc_out=None, collect_routes=False, spec=False):
    """x: (B,S,d) embedded input. Returns (y, new_caches, aux).

    ``collect_routes=True`` (MoE serving) additionally stacks the per-layer
    routed expert ids into ``aux["route_ids"]`` (L, T, k) so the engine can
    drive the runtime expert cache (DESIGN.md §3).

    ``caches`` may also be a function of the layer index that returns
    that layer's cache (a paged pool read in place, latent attention);
    ``new_caches`` are then the (L, B, S, ...) entries the layers wrote.

    ``spec=True`` (speculative decode, DESIGN.md §17) runs S>=1 new tokens
    through the LIVE-cache attention path (masked ring writes, no
    prefill-from-empty rewrite) and pins the MoE dispatch capacity at the
    full token count so the batched verify forward is drop-free — plain
    decode and verify then score identical distributions."""
    if collect_routes and cfg.moe is None:
        raise ValueError("collect_routes needs routed experts")
    # scan carries must have a fixed structure: pre-seed the aux keys
    zero = jnp.zeros((), jnp.float32)
    aux_total: Dict[str, Any] = \
        {"load_balance": zero, "router_z": zero} if (cfg.moe and train) \
        else {}
    # Serving paths carry pad/idle rows tagged position=-1; keep them out
    # of the MoE dispatch (train positions are always valid — skip the op).
    token_valid = (positions >= 0) if (caches is not None
                                       and cfg.moe is not None) else None
    # drop-free capacity for the speculative paths: per-expert routed
    # assignments are bounded by T = B*S, so cap >= T can never displace
    # a token (the formula's cap scales with T and would otherwise drop
    # DIFFERENT tokens at draft vs verify widths, breaking exactness)
    moe_capacity = x.shape[0] * x.shape[1] if spec else None

    def block(carry, xs, dense=False):
        x, aux = carry
        p, cache = xs
        h, new_kv = L.attention(
            p["attn"], L.rms_norm(x, p["attn_norm"]["scale"]),
            cfg.attention, positions=positions, cache=cache, spec=spec)
        x = L.constrain(x + h, "residual")
        if enc_out is not None:
            h, _ = L.attention(
                p["cross_attn"],
                L.rms_norm(x, p["cross_attn_norm"]["scale"]),
                cfg.attention, positions=positions, kv_x=enc_out)
            x = L.constrain(x + h, "residual")
        xn = L.rms_norm(x, p["ffn_norm"]["scale"])
        if dense:
            h, ids = L.mlp(p["mlp"], xn, cfg.act), None
        else:
            h, aux, ids = _ffn_or_moe(p, xn, cfg, par, train, use_kernel,
                                      aux, token_valid=token_valid,
                                      moe_capacity=moe_capacity)
        ys = (new_kv, ids) if collect_routes and not dense else new_kv
        return (L.constrain(x + h, "residual"), aux), ys

    k = cfg.first_dense_layers
    stacks = [("layers", _maybe_remat(block, cfg), k, cfg.num_layers)]
    if k:
        # leading dense layers: the first k layers of the cache; their
        # routes are not collected (no experts)
        stacks.insert(0, ("dense_layers", _maybe_remat(
            functools.partial(block, dense=True), cfg), 0, k))
    ring, view = caches, None
    if callable(caches):
        # a per-layer view (a paged pool read in place): each layer
        # returns only the entries it wrote
        view = caches
    elif caches is not None and k:
        view = lambda i: jax.tree_util.tree_map(lambda a: a[i], ring)

    def by_index(fn):
        return lambda carry, xs: fn(carry, (xs[0], view(xs[1])))

    outs = []
    for stack, fn, lo, hi in stacks:
        if view is None:
            (x, aux_total), ys = _scan_or_loop(
                fn, (x, aux_total), (params[stack], caches), cfg)
        else:
            (x, aux_total), ys = _scan_or_loop(
                by_index(fn), (x, aux_total),
                (params[stack], jnp.arange(lo, hi)), cfg)
        outs.append(ys)
    if collect_routes:
        new_caches, route_ids = outs[-1]
        aux_total = dict(aux_total, route_ids=route_ids)
    else:
        new_caches = outs[-1]
    if k and new_caches is not None:
        new_caches = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b]), outs[0], new_caches)
    if (ring is not None and not callable(ring) and x.shape[1] == 1
            and cfg.attention.latent):
        # a latent decode step returns the entries it wrote: store them
        new_caches = L.write_entries(ring, new_caches)
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# RWKV6 stack
# ---------------------------------------------------------------------------

def rwkv_forward(params, cfg: ModelConfig, x, positions, *, caches=None,
                 **_):
    def block(carry, xs):
        x, _ = carry
        p, cache = xs
        tm_cache = None if cache is None else \
            {"state": cache["state"], "x_att": cache["x_att"]}
        h, tm_new = S.rwkv6_timemix(
            p["rwkv"], L.rms_norm(x, p["attn_norm"]["scale"]), cfg.ssm,
            tm_cache)
        x = x + h
        cm_cache = None if cache is None else {"x_ffn": cache["x_ffn"]}
        h, cm_new = S.rwkv6_channelmix(
            p["rwkv"], L.rms_norm(x, p["ffn_norm"]["scale"]), cm_cache)
        new_cache = {**tm_new, **cm_new}
        return (x + h, None), new_cache

    body = _maybe_remat(block, cfg)
    (x, _), new_caches = _scan_or_loop(
        body, (x, None), (params["layers"], caches), cfg)
    return x, new_caches, {}


# ---------------------------------------------------------------------------
# Zamba2 hybrid: [shared-attn, 6x mamba2] x 13 + [shared-attn, 3x mamba2]
# ---------------------------------------------------------------------------

def _hybrid_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(num_full_groups, group_size, remainder_layers)."""
    g = cfg.attn_every
    full = cfg.num_layers // g
    rem = cfg.num_layers - full * g
    if rem == 0:           # keep >=1 layer in the tail for the final attn
        full -= 1
        rem = g
    return full, g, rem


def _shared_attn_block(shared, cfg, x, positions, cache):
    h, new_kv = L.attention(
        shared["attn"], L.rms_norm(x, shared["attn_norm"]["scale"]),
        cfg.attention, positions=positions, cache=cache)
    x = x + h
    x = x + L.mlp(shared["mlp"],
                  L.rms_norm(x, shared["ffn_norm"]["scale"]), cfg.act)
    return x, new_kv


def hybrid_forward(params, cfg: ModelConfig, x, positions, *, caches=None,
                   **_):
    full, g, rem = _hybrid_layout(cfg)
    shared = params["shared"]
    mamba_p = params["layers"]
    take = lambda t, a, b: jax.tree_util.tree_map(lambda v: v[a:b], t)
    head_p = take(mamba_p, 0, full * g)
    head_p = jax.tree_util.tree_map(
        lambda v: v.reshape((full, g) + v.shape[1:]), head_p)
    tail_p = take(mamba_p, full * g, cfg.num_layers)

    m_caches = None if caches is None else caches["mamba"]
    a_caches = None if caches is None else caches["attn"]
    head_c = tail_c = a_head_c = a_tail_c = None
    if caches is not None:
        head_c = jax.tree_util.tree_map(
            lambda v: v[:full * g].reshape((full, g) + v.shape[1:]),
            m_caches)
        tail_c = take(m_caches, full * g, cfg.num_layers)
        a_head_c = take(a_caches, 0, full)
        a_tail_c = take(a_caches, full, full + 1)

    def mamba_body(x, xs):
        p, cache = xs
        h, new_c = S.mamba2_block(
            p["mamba"], L.rms_norm(x, p["attn_norm"]["scale"]), cfg.ssm,
            cache)
        return x + h, new_c

    mamba_body = _maybe_remat(mamba_body, cfg)

    def group_body(x, xs):
        p_g, mc_g, ac_g = xs
        x, new_kv = _shared_attn_block(shared, cfg, x, positions, ac_g)
        x, new_mc = jax.lax.scan(mamba_body, x, (p_g, mc_g))
        return x, (new_mc, new_kv)

    x, (new_head_mc, new_head_ac) = jax.lax.scan(
        group_body, x, (head_p, head_c, a_head_c))

    # tail: one more shared-attn application + remaining mamba layers
    tail_ac = None if a_tail_c is None else jax.tree_util.tree_map(
        lambda v: v[0], a_tail_c)
    x, new_tail_ac = _shared_attn_block(shared, cfg, x, positions, tail_ac)
    x, new_tail_mc = jax.lax.scan(mamba_body, x, (tail_p, tail_c))

    new_caches = None
    if caches is not None:
        flat_mc = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate(
                [a.reshape((full * g,) + a.shape[2:]), b]),
            new_head_mc, new_tail_mc)
        flat_ac = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b[None]]),
            new_head_ac, new_tail_ac)
        new_caches = {"mamba": flat_mc, "attn": flat_ac}
    return x, new_caches, {}


FORWARDS = {
    "dense": decoder_forward,
    "moe": decoder_forward,
    "vlm": decoder_forward,
    "ssm": rwkv_forward,
    "hybrid": hybrid_forward,
}
