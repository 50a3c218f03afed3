"""Shared neural layers (pure functions over param pytrees, no flax).

Conventions:
  * params are nested dicts keyed by the names in ModelConfig.param_shapes()
  * activations are bf16, reductions/norms/softmax in f32
  * attention supports GQA (kv<heads), MQA (kv=1), sliding-window (ring
    buffer KV cache), qk-norm, cross-attention, causal & bidirectional
  * decode caches carry explicit absolute-position tags so SWA ring buffers
    mask correctly
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttentionConfig
from repro.dist.sharding import constrain


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                          # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention factor for a context stretched ``scale`` times."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rotary_inv_freq(acfg: AttentionConfig, dim: int) -> np.ndarray:
    """(dim // 2,) float32 inverse frequencies of a rotary of width
    ``dim``: theta's ladder, or with ``acfg.yarn`` the blend of its
    interpolated (``/ factor``) and extrapolated frequencies over the
    dimensions between the ``beta_fast`` and ``beta_slow`` rotation
    counts at ``original_max_position_embeddings``."""
    base = float(acfg.rope_theta)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = acfg.yarn
    if y is None:
        return extra.astype(np.float32)

    def corr(rotations):
        return dim * math.log(y.original_max_position_embeddings
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / (high - low if high > low else 0.001), 0.0, 1.0)
    keep = 1.0 - ramp                      # share kept extrapolated
    return (extra / y.factor * ramp + extra * keep).astype(np.float32)


def _rotary(acfg: AttentionConfig, x: jax.Array,
            positions: jax.Array) -> jax.Array:
    """Rotary of x (B, S, H, d) at ``positions`` (B, S) on interleaved
    pairs (x[2i], x[2i+1]), as DeepSeek-V2's complex form turns them,
    at ``rotary_inv_freq``; YaRN scales cos and sin by mscale(factor,
    mscale) / mscale(factor, mscale_all_dim)."""
    d = x.shape[-1]
    ang = positions[..., None].astype(jnp.float32) \
        * jnp.asarray(rotary_inv_freq(acfg, d))
    y = acfg.yarn
    m = 1.0 if y is None else \
        yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor,
                                                     y.mscale_all_dim)
    cos = (jnp.cos(ang) * m)[..., None, :]
    sin = (jnp.sin(ang) * m)[..., None, :]
    xr = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# KV cache: fixed-size ring buffer (window = sliding_window or max length),
# slots tagged with absolute positions (-1 = empty).
# --------------------------------------------------------------------------

def init_kv_cache(batch: int, window: int, num_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros((batch, window, num_kv, head_dim), dtype),
        "v": jnp.zeros((batch, window, num_kv, head_dim), dtype),
        "pos": jnp.full((batch, window), -1, jnp.int32),
    }


def _update_cache(cache, k_new, v_new, positions):
    """Insert S_new entries at slots ``position % window`` (vectorized)."""
    window = cache["k"].shape[1]
    slots = positions % window                                 # (B, S_new)
    b_idx = jnp.arange(k_new.shape[0])[:, None]
    k = constrain(cache["k"].at[b_idx, slots].set(k_new), "kv_cache")
    v = constrain(cache["v"].at[b_idx, slots].set(v_new), "kv_cache")
    pos = cache["pos"].at[b_idx, slots].set(positions)
    return {"k": k, "v": v, "pos": pos}


def _spec_update_cache(cache, k_new, v_new, positions):
    """Ring-buffer insert that DROPS rows tagged position<0.

    The speculative paths (draft + batched verify, DESIGN.md §17) carry
    right-padded draft tails and idle decode slots as position=-1; the
    plain modulo scatter would alias them onto slot ``(-1) % window ==
    window - 1`` and clobber a live entry. Masked rows are redirected to
    the out-of-range slot ``window`` and silently dropped by the scatter
    (same sentinel trick as the paged-KV table scatter)."""
    window = cache["k"].shape[1]
    live = positions >= 0                                      # (B, S_new)
    slots = jnp.where(live, positions % window, window)
    b_idx = jnp.arange(k_new.shape[0])[:, None]
    k = constrain(cache["k"].at[b_idx, slots].set(k_new, mode="drop"),
                  "kv_cache")
    v = constrain(cache["v"].at[b_idx, slots].set(v_new, mode="drop"),
                  "kv_cache")
    pos = cache["pos"].at[b_idx, slots].set(positions, mode="drop")
    return {"k": k, "v": v, "pos": pos}


def _ring_prefill(cache, new: Dict[str, jax.Array], positions):
    """Prefill-from-empty ring write of the ``new`` leaves (B, S, ...)
    WITHOUT a scatter.

    Positions are contiguous 0..S-1, so the ring-buffer content is a
    (rolled) slice of the leaves — a reshape GSPMD partitions for free,
    vs. the general scatter which all-gathers the whole cache per layer."""
    s = positions.shape[1]
    window = cache["pos"].shape[1]
    if s >= window:
        shift = (s - window) % window      # slot of the first kept entry
        cut = lambda a: jnp.roll(a[:, -window:], shift, axis=1)
        out = {k: cut(v) for k, v in new.items()}
        pos = cut(positions)
    else:
        out = {k: jnp.pad(v, [(0, 0), (0, window - s)]
                          + [(0, 0)] * (v.ndim - 2))
               for k, v in new.items()}
        pos = jnp.pad(positions, [(0, 0), (0, window - s)],
                      constant_values=-1)
    out = {k: v.astype(cache[k].dtype) for k, v in out.items()}
    out["pos"] = pos.astype(jnp.int32)
    return out


def _prefill_cache(cache, k_new, v_new, positions):
    # NOTE: no sharding constraint here — constraining would CSE with the
    # in-context attention's k/v and drag a seq-gather into every layer;
    # the stacked cache output is resharded once at the jit boundary.
    return _ring_prefill(cache, {"k": k_new, "v": v_new}, positions)


_Q_CHUNK = 512      # query-block size for long-sequence attention


def _sdpa_block(q, k, v, mask, scale, score_name):
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = constrain(logits, score_name)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _sdpa_grouped_block(q, k, v, mask, scale, score_name: str) -> jax.Array:
    """GQA without materializing repeated K/V: queries are reshaped to
    (B, Sq, Hkv, G, hd) and contract the SHARED kv head dim directly —
    the K/V cache is read once, not G times (§Perf kimi-decode iter 3:
    the expand+transpose copy was the decode-path's dominant HBM term,
    and the dK/dV all-reduce shrinks G-fold in training backward).
    """
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    q5 = q.reshape(b, sq, hkv, g, hd)
    logits = jnp.einsum("bqcgd,bkcd->bcgqk", q5, k,
                        preferred_element_type=jnp.float32) * scale
    logits = constrain(logits, score_name)
    logits = jnp.where(mask[:, :, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bcgqk,bkcd->bqcgd", probs, v)
    return out.reshape(b, sq, h, hd)


def _sdpa(q, k, v, mask, score_name: str, grouped: bool = True
          ) -> jax.Array:
    """q: (B,Sq,H,hd) k,v: (B,Sk,Hkv,hd) mask: (B,1,Sq,Sk) bool.

    GQA (hkv < h) always runs the grouped contraction — K/V are never
    expanded. Long queries are processed in blocks of _Q_CHUNK (scan) so
    the score tensor is O(chunk x Sk), never O(Sq x Sk) — flash-style
    memory bound, exact softmax (each block sees all of K)."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    grouped = grouped and hkv != h
    if not grouped and hkv != h:
        # flat + head-sharded path (heads_ok archs): expand K/V; the
        # expansion shards over "model" with the scores
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    block = _sdpa_grouped_block if grouped else _sdpa_block
    name = score_name + ("_g" if grouped else "")
    scale = hd ** -0.5
    if sq <= 2 * _Q_CHUNK or sq % _Q_CHUNK:
        return block(q, k, v, mask, scale, name)
    nb = sq // _Q_CHUNK
    qs = q.reshape(b, nb, _Q_CHUNK, h, hd).transpose(1, 0, 2, 3, 4)
    ms = mask.reshape(b, 1, nb, _Q_CHUNK, -1).transpose(2, 0, 1, 3, 4)

    def body(_, qm):
        qb, mb = qm
        return None, block(qb, k, v, mb, scale, name)

    _, out = jax.lax.scan(body, None, (qs, ms))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


def attention(p: Dict[str, Any], x: jax.Array, acfg: AttentionConfig, *,
              positions: jax.Array,
              cache: Optional[Dict[str, jax.Array]] = None,
              kv_x: Optional[jax.Array] = None,
              use_rope: bool = True,
              spec: bool = False,
              ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Self- or cross-attention.

    x: (B, S, d); positions: (B, S) absolute positions of x.
    cache=None -> full attention over (kv_x or x) with causal/SWA mask.
    cache given -> decode/prefill-with-cache: new k/v are written into the
    ring buffer, attention runs over the buffer with position-tag masking.
    kv_x -> cross-attention (no causal mask, no rope on kv side by default).
    spec -> speculative multi-token decode (DESIGN.md §17): S>=1 new
    tokens extend a LIVE cache (never the prefill-from-empty rewrite) and
    rows tagged position=-1 are dropped instead of aliased by the modulo.
    """
    if acfg.latent:
        return latent_attention(p, x, acfg, positions=positions,
                                cache=cache, spec=spec)
    b, s, d = x.shape
    h, hkv, hd = acfg.num_heads, acfg.num_kv_heads, acfg.head_dim
    src = kv_x if kv_x is not None else x

    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (src @ p["wk"]).reshape(b, src.shape[1], hkv, hd)
    v = (src @ p["wv"]).reshape(b, src.shape[1], hkv, hd)

    if acfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])

    cross = kv_x is not None
    if use_rope and not cross:
        q = rope(q, positions, acfg.rope_theta)
        k = rope(k, positions, acfg.rope_theta)

    # Full-attention paths: grouped GQA only when heads can't shard over
    # "model" — measured (§Perf): flat+head-sharded beats grouped's 5D
    # layout transitions for heads_ok archs (mixtral train 833->3213 GB
    # collectives with grouped), while grouped+q-sharded wins 8x for
    # 15-head smollm. Decode always groups (K/V never expanded).
    from repro.dist.sharding import full_grouped_ok
    g_full = full_grouped_ok(h, hkv)

    new_cache = None
    if cross:
        # bidirectional over the (precomputed) source; mask only padding-free
        mask = jnp.ones((b, 1, s, src.shape[1]), bool)
        out = _sdpa(q, k, v, mask, "attn_scores_full", grouped=g_full)
    elif cache is not None and s > 1 and not spec:
        # prefill-from-empty: attend over the in-context k/v directly
        # (heads-sharded, zero extra comm) and write the ring buffer for
        # the decode steps that follow. Attending *through* the window-
        # sharded cache would psum every softmax (see DESIGN.md §4).
        new_cache = _prefill_cache(cache, k, v, positions)
        qpos = positions
        # key validity: right-padded slot prefills tag pads with pos=-1;
        # they must never be attended (and their ring-buffer entries stay
        # tagged invalid for the decode steps that follow)
        mask = (qpos[:, None, :, None] >= qpos[:, None, None, :]) \
            & (qpos[:, None, None, :] >= 0)
        if acfg.sliding_window:
            mask &= (qpos[:, None, :, None] - qpos[:, None, None, :]
                     < acfg.sliding_window)
        out = _sdpa(q, k, v, mask, "attn_scores_full", grouped=g_full)
    elif cache is not None:
        # decode (S==1) or speculative draft/verify (spec=True, S>=1):
        # the position-tag mask below is already exact for S>1 queries —
        # each query row attends its own causal window over the buffer.
        writer = _spec_update_cache if spec else _update_cache
        new_cache = writer(cache, k, v, positions)
        kpos = new_cache["pos"]                                  # (B, W)
        qpos = positions                                         # (B, S)
        valid = kpos[:, None, None, :] >= 0
        causal = kpos[:, None, None, :] <= qpos[:, None, :, None]
        mask = valid & causal
        if acfg.sliding_window:
            mask &= (qpos[:, None, :, None] - kpos[:, None, None, :]
                     < acfg.sliding_window)
        out = _sdpa(q, new_cache["k"], new_cache["v"], mask,
                    "attn_scores_cache", grouped=True)
    else:
        qpos = positions
        mask = qpos[:, None, :, None] >= qpos[:, None, None, :] \
            if acfg.causal else jnp.ones((b, 1, s, s), bool)
        if acfg.causal and acfg.sliding_window:
            mask &= (qpos[:, None, :, None] - qpos[:, None, None, :]
                     < acfg.sliding_window)
        out = _sdpa(q, k, v, mask, "attn_scores_full", grouped=g_full)

    return out.reshape(b, s, h * hd) @ p["wo"], new_cache


def _attend(q, k, v, mask, scale: float) -> jax.Array:
    """q, k: (B, S, H, dq); v: (B, Sk, H, dv); mask (B, 1, Sq, Sk).
    Query blocks of _Q_CHUNK past 2 * _Q_CHUNK, exact softmax."""
    def block(qb, mb):
        logits = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mb, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhe->bqhe", probs, v)

    b, sq, h, _ = q.shape
    if sq <= 2 * _Q_CHUNK or sq % _Q_CHUNK:
        return block(q, mask)
    nb = sq // _Q_CHUNK
    qs = q.reshape(b, nb, _Q_CHUNK, h, -1).transpose(1, 0, 2, 3, 4)
    ms = mask.reshape(b, 1, nb, _Q_CHUNK, -1).transpose(2, 0, 1, 3, 4)
    _, out = jax.lax.scan(lambda _, qm: (None, block(*qm)), None, (qs, ms))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, -1)


def latent_scale(acfg: AttentionConfig) -> float:
    """Softmax scale of latent attention: the query/key head width's
    inverse square root, times YaRN's mscale(factor, mscale_all_dim)
    squared (DeepSeek-V2's published modeling code)."""
    scale = (acfg.head_dim + acfg.qk_rope_head_dim) ** -0.5
    y = acfg.yarn
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def latent_attention(p: Dict[str, Any], x: jax.Array,
                     acfg: AttentionConfig, *, positions: jax.Array,
                     cache: Optional[Dict[str, jax.Array]] = None,
                     spec: bool = False,
                     ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434).

    ``wkv_a`` gives the latent ``c`` (``kv_lora_rank``, through its
    RMSNorm) and one rotary key shared by the heads; the cache holds
    their concatenation, ``kv`` (B, W, kv_lora_rank + qk_rope_head_dim),
    one vector a position, beside the position tags ``pos``.

    Without a cache, and for a prefill (S > 1) into a fresh one,
    attention runs expanded: ``wkv_b`` up-projects ``c`` to every head's
    no-position key and value; the prefill returns the written ring.

    A decode step (S == 1) runs absorbed and reads ``cache`` in place:
    the key half of ``wkv_b`` is folded into the query, scores are taken
    against the cached latents of the positions before the token (inside
    the ring) and against the token's own latent, and the value half is
    applied to the attended latent. It returns only the entry it wrote,
    ``{"kv": (B, 1, ...), "pos": (B, 1)}``, for the caller to store."""
    if spec:
        raise NotImplementedError("speculative steps over a latent cache")
    b, s, _ = x.shape
    h, hd, rd = acfg.num_heads, acfg.head_dim, acfg.qk_rope_head_dim
    r, vd = acfg.kv_lora_rank, acfg.v_head_dim
    q = (x @ p["wq"]).reshape(b, s, h, hd + rd)
    q_nope, q_rope = q[..., :hd], _rotary(acfg, q[..., hd:], positions)
    kv = x @ p["wkv_a"]
    c = rms_norm(kv[..., :r], p["kv_norm"]["scale"])
    k_rope = _rotary(acfg, kv[:, :, None, r:], positions)[:, :, 0]
    latent = jnp.concatenate([c, k_rope], axis=-1)       # (B, S, r + rd)
    w_kv = p["wkv_b"].reshape(r, h, hd + vd)
    scale = latent_scale(acfg)
    if cache is not None and s == 1:
        ring, kpos = cache["kv"], cache["pos"]             # (B, W, r + rd)
        qpos = positions[:, :, None]                       # (B, 1, 1)
        # the ring's entries before the token and inside the window (its
        # own slot's old entry is the one the write evicts)
        seen = (kpos[:, None, :] >= 0) & (kpos[:, None, :] < qpos) \
            & (qpos - kpos[:, None, :] < kpos.shape[1])
        if acfg.sliding_window:
            seen &= qpos - kpos[:, None, :] < acfg.sliding_window
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_kv[..., :hd],
                           preferred_element_type=jnp.float32)
        qc = jnp.concatenate([q_lat.astype(x.dtype), q_rope], axis=-1)
        logits = jnp.concatenate([
            jnp.einsum("bshc,bwc->bhsw", qc, ring,
                       preferred_element_type=jnp.float32),
            jnp.einsum("bshc,bsc->bhs", qc, latent,
                       preferred_element_type=jnp.float32)[..., None],
        ], axis=-1) * scale
        mask = jnp.concatenate([seen, (qpos >= 0)], axis=-1)[:, None]
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        w = ring.shape[1]
        o_lat = jnp.einsum("bhsw,bwr->bshr", probs[..., :w], ring[..., :r]) \
            + jnp.einsum("bhs,bsr->bshr", probs[..., w], c)
        out = jnp.einsum("bshr,rhv->bshv", o_lat, w_kv[..., hd:])
        new_cache = {"kv": latent.astype(ring.dtype), "pos": positions}
    else:
        kvb = jnp.einsum("bsr,rhe->bshe", c, w_kv)
        k = jnp.concatenate(
            [kvb[..., :hd],
             jnp.broadcast_to(k_rope[:, :, None], (b, s, h, rd))], axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        qpos = positions
        mask = qpos[:, None, :, None] >= qpos[:, None, None, :]
        new_cache = None
        if cache is not None:
            # right-padded slot prefills tag pads with pos=-1
            mask &= qpos[:, None, None, :] >= 0
            new_cache = _ring_prefill(cache, {"kv": latent}, positions)
        if acfg.sliding_window:
            mask &= (qpos[:, None, :, None] - qpos[:, None, None, :]
                     < acfg.sliding_window)
        out = _attend(qf, k, kvb[..., hd:], mask, scale)
    return out.reshape(b, s, h * vd) @ p["wo"], new_cache


def write_entries(ring: Dict[str, jax.Array],
                  entries: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Store latent decode entries (L, B, S, ...), tagged by their
    positions, into a stacked ring cache (L, B, W, ...); rows tagged
    position<0 are dropped."""
    window = ring["pos"].shape[2]
    pos = entries["pos"][0]                                    # (B, S)
    slots = jnp.where(pos >= 0, pos % window, window)
    b_idx = jnp.arange(pos.shape[0])[:, None]
    return {k: ring[k].at[:, b_idx, slots].set(entries[k], mode="drop")
            for k in ring}


def mlp(p: Dict[str, Any], x: jax.Array, act: str) -> jax.Array:
    if act == "swiglu":
        return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    if act == "gelu":
        return jax.nn.gelu(x @ p["w_up"], approximate=True) @ p["w_down"]
    if act == "relu_sq":
        return jnp.square(jax.nn.relu(x @ p["w_up"])) @ p["w_down"]
    raise ValueError(act)


def embed(table: jax.Array, ids: jax.Array) -> jax.Array:
    return jnp.take(table, ids, axis=0)


def unembed(table: jax.Array, x: jax.Array) -> jax.Array:
    """(B,S,d) @ (V,d)^T -> (B,S,V) logits in f32 for a stable softmax."""
    return jnp.einsum("bsd,vd->bsv", x, table,
                      preferred_element_type=jnp.float32)


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 vocab_size: int) -> jax.Array:
    """Mean NLL with padded-vocab masking (positions with label<0 ignored)."""
    v_pad = logits.shape[-1]
    if v_pad > vocab_size:
        pad_mask = jnp.arange(v_pad) >= vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return (nll * valid).sum() / jnp.maximum(valid.sum(), 1)
