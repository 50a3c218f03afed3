"""build_model(cfg) -> Model: init / train loss / prefill / decode_step,
uniform across all 10 architectures (+ the paper's mixtral-mop serving
config). Frontend stubs (audio/vision) consume precomputed embeddings.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import mixed_moe
from repro.core.precision_plan import PrecisionPlan
from repro.models import layers as L
from repro.models.encdec import encdec_forward, encoder_forward
from repro.models.transformer import FORWARDS, _ffn_or_moe, _hybrid_layout


# ---------------------------------------------------------------------------
# Parameter init (name-rule based; shapes from cfg.param_shapes())
# ---------------------------------------------------------------------------

def _init_one(key, name: str, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    if last in ("scale", "norm", "ln_x", "D"):
        return jnp.ones(shape, dtype)
    if last == "A_log":
        # mamba2: A in [1, 16]
        return jnp.log(jnp.linspace(1.0, 16.0, shape[0])).astype(dtype)
    if last == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # inv softplus
    if last in ("mix", "ffn_mix"):
        return jnp.full(shape, 0.5, dtype)
    if last == "decay_base":
        return jnp.zeros(shape, dtype)
    if last == "bonus":
        return (jax.random.normal(key, shape, jnp.float32) * 0.1
                ).astype(dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for name, v in flat.items():
        node = out
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    shapes = cfg.param_shapes()
    keys = jax.random.split(key, len(shapes))
    flat = {}
    for k, (name, shape) in zip(keys, shapes):
        flat[name] = _init_one(k, name, shape, dtype)
    return nest(flat)


def abstract_params(cfg: ModelConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct pytree (dry-run: no allocation)."""
    dtype = jnp.dtype(cfg.dtype)
    return nest({name: jax.ShapeDtypeStruct(shape, dtype)
                 for name, shape in cfg.param_shapes()})


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               abstract: bool = False) -> Any:
    """Decode caches; ``abstract=True`` returns ShapeDtypeStructs."""
    dt = jnp.dtype(cfg.dtype)
    mk = (lambda s, d=dt: jax.ShapeDtypeStruct(s, d)) if abstract \
        else (lambda s, d=dt: jnp.zeros(s, d) if d != jnp.int32
              else jnp.full(s, -1, d))

    def kv(n, window):
        return {"k": mk((n, batch, window, cfg.attention.num_kv_heads,
                         cfg.attention.head_dim)),
                "v": mk((n, batch, window, cfg.attention.num_kv_heads,
                         cfg.attention.head_dim)),
                "pos": mk((n, batch, window), jnp.int32)}

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        window = min(max_len, cfg.attention.sliding_window or max_len)
        return kv(cfg.num_layers, window)
    if fam == "encdec":
        window = max_len
        return {"self": kv(cfg.num_layers, window),
                "enc_out": mk((batch, cfg.frontend_len, cfg.d_model))}
    if fam == "ssm":   # rwkv6
        h = cfg.d_model // cfg.ssm.head_dim
        n = cfg.num_layers
        return {"state": mk((n, batch, h, cfg.ssm.head_dim,
                             cfg.ssm.head_dim), jnp.float32),
                "x_att": mk((n, batch, cfg.d_model)),
                "x_ffn": mk((n, batch, cfg.d_model))}
    if fam == "hybrid":
        di = cfg.ssm.expand * cfg.d_model
        h = di // cfg.ssm.head_dim
        n = cfg.num_layers
        full, g, rem = _hybrid_layout(cfg)
        n_attn = full + 1
        window = min(max_len, cfg.attention.sliding_window or max_len)
        conv_ch = di + 2 * cfg.ssm.state_dim
        return {
            "mamba": {"state": mk((n, batch, h, cfg.ssm.head_dim,
                                   cfg.ssm.state_dim), jnp.float32),
                      "conv": mk((n, batch, 3, conv_ch))},
            "attn": kv(n_attn, window),
        }
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# Paged KV cache (DESIGN.md §13)
# ---------------------------------------------------------------------------
#
# The slot cache above allocates every slot its full ring window up front;
# a short request strands (window - len) entries of HBM for its whole
# lifetime. The paged cache keeps ONE pool of fixed-size pages plus a
# host-side per-slot page table: slot ``b``'s ring buffer is the
# concatenation of its mapped pages, chunk ``j`` of the ring living in
# physical page ``table[b, j]``. Pages are mapped on demand as positions
# advance and freed on retire, so allocated KV bytes track actual tokens
# (per page), not slots x window.
#
# Page 0 is the reserved NULL page: all position tags -1, never allocated,
# never written (scatters remap null entries to an out-of-range sentinel
# and drop them). An unmapped chunk therefore gathers as an all-invalid
# ring segment — masked to exactly 0 contribution by the attention's
# position tags — which makes decode through the paged cache BIT-IDENTICAL
# to the slot cache for the same stream (tested): the gathered ring is
# sliced to exactly the window width, so every attention sees the same
# operand tensors in the same order.

@dataclasses.dataclass(frozen=True)
class PagedKVMeta:
    """Static layout of a paged KV pool."""
    window: int           # logical ring width per slot (== slot-cache W)
    page_size: int        # tokens per page
    chunks_per_slot: int  # ceil(window / page_size)
    num_pages: int        # physical pages incl. the reserved null page 0


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                     page_size: int = 16,
                     num_pages: Optional[int] = None,
                     abstract: bool = False
                     ) -> Tuple[Any, PagedKVMeta]:
    """Paged decode cache: (pool, meta). ``num_pages=None`` sizes the pool
    at worst case (every slot fully windowed) + the null page; a smaller
    pool reclaims HBM for the frontier's residency axis (the engine caps
    admission so allocation can never dead-end mid-flight)."""
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"family {cfg.family} has no paged KV path")
    window = min(max_len, cfg.attention.sliding_window or max_len)
    chunks = -(-window // page_size)
    if num_pages is None:
        num_pages = batch * chunks + 1
    if num_pages < chunks + 1:
        raise ValueError(f"pool of {num_pages} pages cannot hold even one "
                         f"full window ({chunks} pages)")
    dt = jnp.dtype(cfg.dtype)
    hkv, hd = cfg.attention.num_kv_heads, cfg.attention.head_dim
    n = cfg.num_layers
    mk = (lambda s, d=dt: jax.ShapeDtypeStruct(s, d)) if abstract \
        else (lambda s, d=dt: jnp.zeros(s, d) if d != jnp.int32
              else jnp.full(s, -1, d))
    pool = {"k": mk((n, num_pages, page_size, hkv, hd)),
            "v": mk((n, num_pages, page_size, hkv, hd)),
            "pos": mk((n, num_pages, page_size), jnp.int32)}
    return pool, PagedKVMeta(window=window, page_size=page_size,
                             chunks_per_slot=chunks, num_pages=num_pages)


def _scatter_table(pt: jax.Array, num_pages: int) -> jax.Array:
    """Unmapped chunks (null page 0) -> out-of-range sentinel so scatters
    with mode="drop" never write the null page."""
    return jnp.where(pt == 0, num_pages, pt)


def _gather_paged(pool, pt, window: int):
    """pool + page table (B, nc) -> the standard ring cache (L, B, W, ...)
    the attention layers consume. The page view is sliced to exactly
    ``window`` so attention operands (and thus logits) are bit-identical
    to the slot cache's."""
    nc = pt.shape[1]

    def g(a):
        x = a[:, pt]                           # (L, B, nc, ps, ...)
        l, b, _, ps = x.shape[:4]
        return x.reshape((l, b, nc * ps) + x.shape[4:])[:, :, :window]

    return {"k": g(pool["k"]), "v": g(pool["v"]), "pos": g(pool["pos"])}


def _scatter_paged(pool, pt, ring, window: int):
    """Write a (possibly updated) ring cache back into its pages. Null
    chunks are dropped (their ring segment is all-invalid by
    construction), so the null page is never dirtied."""
    nc = pt.shape[1]
    ps = pool["pos"].shape[2]
    spt = _scatter_table(pt, pool["pos"].shape[1])
    pad = nc * ps - window
    out = {}
    for key in ("k", "v", "pos"):
        r = ring[key]
        if pad:
            cfgp = [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (r.ndim - 3)
            r = jnp.pad(r, cfgp,
                        constant_values=-1 if key == "pos" else 0)
        l, b = r.shape[0], r.shape[1]
        rr = r.reshape((l, b, nc, ps) + r.shape[3:])
        out[key] = pool[key].at[:, spt].set(rr, mode="drop")
    return out


def _gather_paged_layer(pool, pt, window: int, layer):
    """Single-layer gather for the per-layer decode pipeline; ``layer``
    is a traced scalar."""
    nc = pt.shape[1]

    def g(a):
        x = a[layer][pt]                       # (B, nc, ps, ...)
        b, _, ps = x.shape[:3]
        return x.reshape((b, nc * ps) + x.shape[3:])[:, :window]

    return {"k": g(pool["k"]), "v": g(pool["v"]), "pos": g(pool["pos"])}


def _scatter_paged_layer(pool, pt, ring, window: int, layer):
    nc = pt.shape[1]
    ps = pool["pos"].shape[2]
    spt = _scatter_table(pt, pool["pos"].shape[1])
    pad = nc * ps - window
    out = {}
    for key in ("k", "v", "pos"):
        r = ring[key]
        if pad:
            cfgp = [(0, 0), (0, pad)] + [(0, 0)] * (r.ndim - 2)
            r = jnp.pad(r, cfgp,
                        constant_values=-1 if key == "pos" else 0)
        b = r.shape[0]
        rr = r.reshape((b, nc, ps) + r.shape[2:])
        out[key] = pool[key].at[layer, spt].set(rr, mode="drop")
    return out


def _scatter_prefill_paged(pool, page_row, ring, window: int):
    """Scatter one slot's freshly prefilled ring (L, W, ...) into its
    mapped pages; ``page_row`` is the slot's (nc,) page-table row (null
    chunks dropped — they hold no written entries)."""
    nc = page_row.shape[0]
    ps = pool["pos"].shape[2]
    spt = _scatter_table(page_row, pool["pos"].shape[1])
    pad = nc * ps - window
    out = {}
    for key in ("k", "v", "pos"):
        r = ring[key]                          # (L, W, ...)
        if pad:
            cfgp = [(0, 0), (0, pad)] + [(0, 0)] * (r.ndim - 2)
            r = jnp.pad(r, cfgp,
                        constant_values=-1 if key == "pos" else 0)
        l = r.shape[0]
        rr = r.reshape((l, nc, ps) + r.shape[2:])
        out[key] = pool[key].at[:, spt].set(rr, mode="drop")
    return out


def paged_reset_pages(pool, pages: jax.Array):
    """Invalidate freed pages' position tags (tags only — k/v bytes are
    dead once every tag is -1, same as ``reset_slot``). ``pages`` is a
    fixed-size (chunks_per_slot,) id vector padded with 0 (the null page,
    remapped to the drop sentinel)."""
    spt = _scatter_table(pages, pool["pos"].shape[1])
    ps = pool["pos"].shape[2]
    fill = jnp.full((pool["pos"].shape[0], pages.shape[0], ps), -1,
                    jnp.int32)
    return dict(pool, pos=pool["pos"].at[:, spt].set(fill, mode="drop"))


# ---------------------------------------------------------------------------
# The Model bundle
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable          # (params, batch) -> (loss, metrics)
    prefill: Callable          # (params, batch, cache) -> (logits, cache)
    decode_step: Callable      # (params, cache, tokens, positions) -> (logits, cache)
    init_cache: Callable
    # Slot-based serving API (continuous batching, DESIGN.md §3); None for
    # families whose decode cache is not the plain ring-buffer KV dict.
    prefill_into_slot: Optional[Callable] = None
    # (params, cache, tokens (1,S), positions (1,S), slot, last_idx)
    #   -> (last-token logits (1,V), cache with slot row replaced)
    decode_step_routed: Optional[Callable] = None
    # (params, cache, tokens, positions) -> (logits, cache, route_ids)
    reset_slot: Optional[Callable] = None
    # (cache, slot) -> cache with the slot's position tags invalidated
    # Per-layer decode hooks (DESIGN.md §12): the engine's async overlap
    # pipeline drives the stack ONE layer at a time so expert transfers
    # for layer L+1 can stage while layer L computes. Splitting the
    # scanned step into embed -> layer^L -> logits is numerically
    # IDENTICAL to decode_step_routed (same primitive sequence; tested
    # bit-exact), it only changes dispatch granularity. None for
    # families without the slot-cache MoE decode path.
    decode_embed: Optional[Callable] = None
    # (params, tokens (B,1)) -> x (B,1,d)
    decode_layer_routed: Optional[Callable] = None
    # (params, cache, x, positions (B,), layer) ->
    #   (x', cache with layer's KV row replaced, route_ids (B, top_k))
    decode_logits: Optional[Callable] = None
    # (params, x (B,1,d)) -> logits (B,V)
    # Paged KV cache (DESIGN.md §13): same serving surface over a page
    # pool + per-slot page table instead of fully-windowed slot rows.
    # Decode through these hooks is bit-identical to the slot-cache path
    # (the gathered page view IS the ring buffer — tested).
    init_paged_cache: Optional[Callable] = None
    # (batch, max_len, *, page_size, num_pages) -> (pool, PagedKVMeta)
    paged_prefill_into_slot: Optional[Callable] = None
    # (params, pool, page_row (nc,), tokens (1,S), positions (1,S),
    #  last_idx, *, window) -> (logits (1,V), pool)
    paged_decode_step_routed: Optional[Callable] = None
    # (params, pool, page_table (B,nc), tokens, positions, *, window)
    #   -> (logits, pool, route_ids)
    paged_decode_layer_routed: Optional[Callable] = None
    # (params, pool, page_table, x, positions, layer, *, window)
    #   -> (x', pool, route_ids (B, top_k))
    paged_reset_pages: Optional[Callable] = None
    # (pool, pages (nc,)) -> pool with the pages' position tags cleared
    # Self-speculative decode (DESIGN.md §17): one multi-token step serves
    # BOTH the draft pass (S=1, draft-rung params) and the batched verify
    # forward (S=K+1, serving params) — rows tagged position=-1 are
    # dropped, the MoE dispatch is drop-free, and attention masks each
    # query to its own causal window, so verify logits at position p are
    # bit-identical to a plain decode step at p.
    spec_step_routed: Optional[Callable] = None
    # (params, cache, tokens (B,S), positions (B,S))
    #   -> (logits (B,S,V), cache, route_ids (L, B*S, top_k))
    paged_spec_step_routed: Optional[Callable] = None
    # (params, pool, page_table (B,nc), tokens (B,S), positions (B,S),
    #  *, window) -> (logits (B,S,V), pool, route_ids)
    rollback_slots: Optional[Callable] = None
    # (cache, keep (B,)) -> cache with tags > keep[b] invalidated per slot
    paged_rollback: Optional[Callable] = None
    # (pool, page_table (B,nc), keep (B,)) -> pool, same contract


def _embed_inputs(params, cfg: ModelConfig, batch):
    """tokens (+ frontend embeddings) -> (x (B,S,d), positions (B,S))."""
    tok = batch["tokens"]
    x = L.embed(params["embed"]["table"], tok) \
        * jnp.asarray(math.sqrt(cfg.d_model), params["embed"]["table"].dtype)
    if cfg.frontend == "vision":
        x = jnp.concatenate([batch["frontend"].astype(x.dtype), x], axis=1)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    return x, positions


def _forward_fn(cfg: ModelConfig):
    if cfg.family == "encdec":
        return encdec_forward
    return FORWARDS[cfg.family]


def build_model(cfg: ModelConfig, mesh=None, *,
                dp_axes: Tuple[str, ...] = ("data",),
                use_kernel: bool = False) -> Model:
    """mesh=None builds a single-device (1,1) mesh (CPU tests)."""
    import contextlib

    from repro.dist import sharding as SH
    if mesh is None:
        dev = np.array(jax.devices()[:1]).reshape(1, 1)
        mesh = jax.sharding.Mesh(dev, ("data", "model"))
        dp_axes = ("data",)
    # token-gather EP (DESIGN.md §4 / §Perf kimi-decode): the "data" axis
    # doubles as the experts' d_ff (FSDP) shard axis; mixed_moe gathers
    # tokens over it instead of re-gathering 1T-scale weights per layer.
    fsdp_axis = "data" if "data" in mesh.shape else None
    par = mixed_moe.MoEParallelism(mesh=mesh, dp_axes=dp_axes,
                                   fsdp_axis=fsdp_axis)
    fwd = _forward_fn(cfg)
    multi_dev = int(np.prod(mesh.devices.shape)) > 1
    act_ctx = (lambda: SH.activation_constraints(cfg, mesh, dp_axes)) \
        if multi_dev else contextlib.nullcontext
    act_ctx_train = (lambda: SH.activation_constraints(
        cfg, mesh, dp_axes, train=True)) if multi_dev \
        else contextlib.nullcontext

    def loss_fn(params, batch):
        with act_ctx_train():
            x, positions = _embed_inputs(params, cfg, batch)
            kw = dict(par=par, train=True, use_kernel=False)
            if cfg.family == "encdec":
                kw["src"] = batch["src"]
            y, _, aux = fwd(params, cfg, x, positions, caches=None, **kw)
            y = L.rms_norm(y, params["final_norm"]["scale"])
            if cfg.frontend == "vision":   # loss over the text tail only
                y = y[:, cfg.frontend_len:]
            logits = L.unembed(params["lm_head"]["table"], y)
            loss = L.softmax_xent(logits, batch["labels"], cfg.vocab_size)
            metrics = {"nll": loss}
            for k, v in aux.items():
                loss = loss + v
                metrics[k] = v
            metrics["loss"] = loss
            return loss, metrics

    def prefill(params, batch, cache):
        with act_ctx():
            x, positions = _embed_inputs(params, cfg, batch)
            kw = dict(par=par, train=False, use_kernel=use_kernel)
            if cfg.family == "encdec":
                kw["src"] = batch["src"]
            y, new_cache, _ = fwd(params, cfg, x, positions, caches=cache,
                                  **kw)
            y = L.rms_norm(y[:, -1:], params["final_norm"]["scale"])
            logits = L.unembed(params["lm_head"]["table"], y)
            return logits[:, 0], new_cache

    def _decode_step(params, cache, tokens, positions, collect_routes):
        """tokens (B,1); positions (B,) absolute position of the token.

        Idle slots pass position=-1: their ring-buffer write lands with an
        invalid (-1) tag, so a retired slot never pollutes its cache row."""
        with act_ctx():
            x = L.embed(params["embed"]["table"], tokens) \
                * jnp.asarray(math.sqrt(cfg.d_model),
                              params["embed"]["table"].dtype)
            pos2 = positions[:, None]
            kw = dict(par=par, train=False, use_kernel=use_kernel)
            if cfg.family == "encdec":
                kw["enc_out"] = cache["enc_out"]
            if collect_routes:
                kw["collect_routes"] = True
            y, new_cache, aux = fwd(params, cfg, x, pos2, caches=cache, **kw)
            y = L.rms_norm(y, params["final_norm"]["scale"])
            logits = L.unembed(params["lm_head"]["table"], y)
            if collect_routes:
                return logits[:, 0], new_cache, aux["route_ids"]
            return logits[:, 0], new_cache

    def decode_step(params, cache, tokens, positions):
        return _decode_step(params, cache, tokens, positions, False)

    slot_api = cfg.family in ("dense", "moe", "vlm") \
        and cfg.frontend == "none"

    def decode_step_routed(params, cache, tokens, positions):
        """decode_step that also returns the per-layer routed expert ids
        (L, B, top_k) in bank order — the engine's expert-cache feed."""
        return _decode_step(params, cache, tokens, positions, True)

    def prefill_into_slot(params, cache, tokens, positions, slot, last_idx):
        """Prefill ONE request into decode slot ``slot`` of a live batch
        cache without touching the other slots (continuous batching,
        DESIGN.md §3).

        tokens/positions: (1, S) RIGHT-padded; pad positions are -1 (the
        attention mask and the ring-buffer tags treat them as invalid).
        ``slot`` and ``last_idx`` (index of the last real token) are traced
        scalars — one compile per padded length, none per slot. Returns
        (next-token logits (1, V), cache with slot row replaced)."""
        window = cache["k"].shape[2]
        with act_ctx():
            x = L.embed(params["embed"]["table"], tokens) \
                * jnp.asarray(math.sqrt(cfg.d_model),
                              params["embed"]["table"].dtype)
            n, _, _, hkv, hd = cache["k"].shape
            sub = {"k": jnp.zeros((n, 1, window, hkv, hd),
                                  cache["k"].dtype),
                   "v": jnp.zeros((n, 1, window, hkv, hd),
                                  cache["v"].dtype),
                   "pos": jnp.full((n, 1, window), -1, jnp.int32)}
            y, new_sub, _ = fwd(params, cfg, x, positions, caches=sub,
                                par=par, train=False, use_kernel=use_kernel)
            y_last = jnp.take(y, last_idx, axis=1, mode="clip")[:, None]
            y_last = L.rms_norm(y_last, params["final_norm"]["scale"])
            logits = L.unembed(params["lm_head"]["table"], y_last)
            merged = {key: cache[key].at[:, slot].set(new_sub[key][:, 0])
                      for key in ("k", "v", "pos")}
            return logits[:, 0], merged

    def reset_slot(cache, slot):
        """Invalidate a retired slot's ring buffer (tags only — k/v bytes
        are dead once every tag is -1)."""
        return dict(cache, pos=cache["pos"].at[:, slot].set(-1))

    # -- per-layer decode (async overlap pipeline, DESIGN.md §12) ----------
    def decode_embed(params, tokens):
        """tokens (B,1) -> embedded x (B,1,d); the pipeline's front."""
        return L.embed(params["embed"]["table"], tokens) \
            * jnp.asarray(math.sqrt(cfg.d_model),
                          params["embed"]["table"].dtype)

    def decode_layer_routed(params, cache, x, positions, layer):
        """One decoder block of the stacked params at index ``layer`` (a
        TRACED scalar — one compile serves every layer). Returns the
        block output, the cache with that layer's KV row replaced, and
        the layer's routed expert ids (B, top_k) in bank order. The body
        is the same block as ``decoder_forward`` — the scanned and the
        per-layer spellings produce identical values."""
        with act_ctx():
            p = jax.tree_util.tree_map(lambda v: v[layer],
                                       params["layers"])
            c = {k: cache[k][layer] for k in ("k", "v", "pos")}
            pos2 = positions[:, None]
            token_valid = pos2 >= 0
            h, new_kv = L.attention(
                p["attn"], L.rms_norm(x, p["attn_norm"]["scale"]),
                cfg.attention, positions=pos2, cache=c)
            x = L.constrain(x + h, "residual")
            xn = L.rms_norm(x, p["ffn_norm"]["scale"])
            h, _, ids = _ffn_or_moe(p, xn, cfg, par, False, use_kernel,
                                    {}, token_valid=token_valid)
            x = L.constrain(x + h, "residual")
            merged = {k: cache[k].at[layer].set(new_kv[k])
                      for k in ("k", "v", "pos")}
            return x, merged, ids

    def decode_logits(params, x):
        """Pipeline tail: final norm + unembed of the last block output."""
        y = L.rms_norm(x, params["final_norm"]["scale"])
        return L.unembed(params["lm_head"]["table"], y)[:, 0]

    # -- paged KV serving hooks (DESIGN.md §13) ------------------------
    def paged_prefill_into_slot(params, pool, page_row, tokens, positions,
                                last_idx, *, window):
        """Paged spelling of ``prefill_into_slot``: same fresh sub-cache
        forward (prefill attends over the in-context k/v, so the logits
        are independent of the cache layout), then the written ring is
        scattered chunk-wise into the slot's mapped pages."""
        with act_ctx():
            x = L.embed(params["embed"]["table"], tokens) \
                * jnp.asarray(math.sqrt(cfg.d_model),
                              params["embed"]["table"].dtype)
            n, _, _, hkv, hd = pool["k"].shape
            sub = {"k": jnp.zeros((n, 1, window, hkv, hd),
                                  pool["k"].dtype),
                   "v": jnp.zeros((n, 1, window, hkv, hd),
                                  pool["v"].dtype),
                   "pos": jnp.full((n, 1, window), -1, jnp.int32)}
            y, new_sub, _ = fwd(params, cfg, x, positions, caches=sub,
                                par=par, train=False, use_kernel=use_kernel)
            y_last = jnp.take(y, last_idx, axis=1, mode="clip")[:, None]
            y_last = L.rms_norm(y_last, params["final_norm"]["scale"])
            logits = L.unembed(params["lm_head"]["table"], y_last)
            ring = {key: new_sub[key][:, 0] for key in ("k", "v", "pos")}
            return logits[:, 0], _scatter_prefill_paged(pool, page_row,
                                                        ring, window)

    def paged_decode_step_routed(params, pool, page_table, tokens,
                                 positions, *, window):
        """Paged ``decode_step_routed``: gather the page view into the
        standard ring cache, run the identical decode step, scatter the
        updated ring back. Bit-identical logits (tested)."""
        ring = _gather_paged(pool, page_table, window)
        logits, new_ring, route_ids = _decode_step(
            params, ring, tokens, positions, True)
        return logits, _scatter_paged(pool, page_table, new_ring,
                                      window), route_ids

    def paged_decode_layer_routed(params, pool, page_table, x, positions,
                                  layer, *, window):
        """Paged spelling of ``decode_layer_routed`` for the overlap
        pipeline; one layer's page view gathered/scattered per call."""
        with act_ctx():
            p = jax.tree_util.tree_map(lambda v: v[layer],
                                       params["layers"])
            c = _gather_paged_layer(pool, page_table, window, layer)
            pos2 = positions[:, None]
            token_valid = pos2 >= 0
            h, new_kv = L.attention(
                p["attn"], L.rms_norm(x, p["attn_norm"]["scale"]),
                cfg.attention, positions=pos2, cache=c)
            x = L.constrain(x + h, "residual")
            xn = L.rms_norm(x, p["ffn_norm"]["scale"])
            h, _, ids = _ffn_or_moe(p, xn, cfg, par, False, use_kernel,
                                    {}, token_valid=token_valid)
            x = L.constrain(x + h, "residual")
            merged = _scatter_paged_layer(pool, page_table, new_kv,
                                          window, layer)
            return x, merged, ids

    # -- self-speculative decode hooks (DESIGN.md §17) -----------------
    def spec_step_routed(params, cache, tokens, positions):
        """Multi-token cached step: tokens/positions (B, S), positions
        RIGHT-padded with -1 past each slot's live span (idle slots are
        all -1). Returns the FULL (B, S, V) logits — the verify path
        scores every position — plus the updated cache and the routed
        expert ids (L, B*S, top_k) with padded rows remapped to the
        sentinel ``num_experts``."""
        with act_ctx():
            x = L.embed(params["embed"]["table"], tokens) \
                * jnp.asarray(math.sqrt(cfg.d_model),
                              params["embed"]["table"].dtype)
            y, new_cache, aux = fwd(params, cfg, x, positions,
                                    caches=cache, par=par, train=False,
                                    use_kernel=use_kernel,
                                    collect_routes=True, spec=True)
            y = L.rms_norm(y, params["final_norm"]["scale"])
            logits = L.unembed(params["lm_head"]["table"], y)
            return logits, new_cache, aux["route_ids"]

    def paged_spec_step_routed(params, pool, page_table, tokens, positions,
                               *, window):
        """Paged spelling of ``spec_step_routed``: gather page view ->
        identical step -> scatter back (bit-identical logits)."""
        ring = _gather_paged(pool, page_table, window)
        logits, new_ring, route_ids = spec_step_routed(
            params, ring, tokens, positions)
        return logits, _scatter_paged(pool, page_table, new_ring,
                                      window), route_ids

    def rollback_slots(cache, keep):
        """Invalidate ring entries past ``keep[b]`` (the last ACCEPTED
        absolute position per slot) — rejected speculative tokens become
        dead tags, exactly like ``reset_slot`` but position-bounded.
        Slots not in the speculative batch pass a large keep value."""
        pos = cache["pos"]
        return dict(cache,
                    pos=jnp.where(pos > keep[None, :, None], -1, pos))

    def paged_rollback(pool, page_table, keep):
        """Paged ``rollback_slots``: the per-slot page view's tags are
        gathered, bounded, and scattered back (null chunks dropped)."""
        pos = pool["pos"][:, page_table]            # (L, B, nc, ps)
        pos = jnp.where(pos > keep[None, :, None, None], -1, pos)
        spt = _scatter_table(page_table, pool["pos"].shape[1])
        return dict(pool,
                    pos=pool["pos"].at[:, spt].set(pos, mode="drop"))

    layered_api = slot_api and cfg.moe is not None

    return Model(
        cfg=cfg,
        init=functools.partial(init_params, cfg),
        loss_fn=loss_fn,
        prefill=prefill,
        decode_step=decode_step,
        init_cache=functools.partial(init_cache, cfg),
        prefill_into_slot=prefill_into_slot if slot_api else None,
        decode_step_routed=decode_step_routed if cfg.moe is not None
        else None,
        reset_slot=reset_slot if slot_api else None,
        decode_embed=decode_embed if layered_api else None,
        decode_layer_routed=decode_layer_routed if layered_api else None,
        decode_logits=decode_logits if layered_api else None,
        init_paged_cache=functools.partial(init_paged_cache, cfg)
        if slot_api else None,
        paged_prefill_into_slot=paged_prefill_into_slot if slot_api
        else None,
        paged_decode_step_routed=paged_decode_step_routed
        if slot_api and cfg.moe is not None else None,
        paged_decode_layer_routed=paged_decode_layer_routed
        if layered_api else None,
        paged_reset_pages=paged_reset_pages if slot_api else None,
        spec_step_routed=spec_step_routed
        if slot_api and cfg.moe is not None else None,
        paged_spec_step_routed=paged_spec_step_routed
        if slot_api and cfg.moe is not None else None,
        rollback_slots=rollback_slots if slot_api else None,
        paged_rollback=paged_rollback if slot_api else None,
    )


# ---------------------------------------------------------------------------
# Applying a MoP PrecisionPlan to trained params (serve layout)
# ---------------------------------------------------------------------------

def apply_precision_plan(params, cfg: ModelConfig, plan: PrecisionPlan):
    """Convert train-layout MoE params into N-bank serve layout: one
    bank per ladder rung (ascending-bits order, e.g. [q4 | q8 | f16]) +
    router column permutation (DESIGN.md §11).

    Works on stacked (L, ...) params; per-layer rung counts are equal by
    construction (balanced plan) so banks stack cleanly."""
    assert cfg.moe is not None
    moe_p = params["layers"]["moe"]
    l = cfg.num_layers
    banks_per_layer = []
    routers = []
    for li in range(l):
        layer_p = {k: moe_p[k][li] for k in ("w_gate", "w_up", "w_down")}
        banks, order = mixed_moe.build_ladder_banks(
            layer_p, plan.bits[li], ladder=plan.ladder,
            group_size=plan.group_size)
        del layer_p     # one layer's slice at a time (2.8 GB at Mixtral widths)
        banks_per_layer.append(banks)
        routers.append(jnp.take(moe_p["router"][li], order, axis=1))
    stacked = {}
    for bank in list(banks_per_layer[0]):
        # pop each bank's per-layer pieces as it is stacked, so at most
        # one bank is held twice (device memory at published widths)
        parts = [b.pop(bank) for b in banks_per_layer]
        stacked[bank] = None if parts[0] is None else \
            jax.tree_util.tree_map(lambda *a: jnp.stack(a), *parts)
        del parts
    new_params = jax.tree_util.tree_map(lambda x: x, params)  # shallow copy
    new_params["layers"] = dict(params["layers"])
    new_params["layers"]["moe"] = {
        "router": jnp.stack(routers),
        "banks": stacked,
    }
    return new_params
