"""Plain float32 reference of the DeepSeek-V2 decoder the configuration
files with ``"arch": "deepseek_v2"`` describe, for the ``correct``
check. It imports nothing of the program: weights are drawn again from
the seed by ``benchlib.weights``, and the experts' rungs come from the
configuration's pinned plan.

It follows DeepSeek-V2's published modeling code (``modeling_deepseek.py``
of deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434). Per layer: RMSNorm;
multi-head latent attention without query compression: the query
projection gives every head ``qk_nope_head_dim`` + ``qk_rope_head_dim``
values, ``kv_a`` gives the latent (``kv_lora_rank``, through its own
RMSNorm) and one rotary key shared by the heads, and ``kv_b``
up-projects the latent to every head's no-position key and value. The
rotary part turns interleaved pairs (x[2i], x[2i+1]), as the published
code's complex form does, at YaRN's frequencies where the configuration
has ``rope_scaling`` (the interpolated and extrapolated frequencies
blended between the ``beta_fast`` and ``beta_slow`` rotation counts),
its cos and sin times mscale(factor, mscale) / mscale(factor,
mscale_all_dim); the softmax scale is (qk_nope + qk_rope) ** -0.5 times
mscale(factor, mscale_all_dim) squared. Causal over the whole sequence. Then RMSNorm
and, in the first ``first_k_dense_replace`` layers, a dense SwiGLU MLP;
in the others a softmax router over ``n_routed_experts``, the top
``num_experts_per_tok`` weights (renormalised only with
``norm_topk_prob``, times ``routed_scaling_factor``), SwiGLU experts,
plus the shared experts as one SwiGLU MLP of ``n_shared_experts`` times
the expert width. Residuals around both. Token embeddings are not
scaled. Expert weights at a quantized rung are quantized as the
configuration states (``references/mixtral.py``'s scheme).

Departures: attention is computed expanded at every position (the
program decodes through the absorbed form over its latent cache, the
same products in another order); nothing else.

``control=True`` computes the same model one precision lower: every
bfloat16 matrix product (attention, shared experts, dense MLP, logits)
as int8 weights by int8 activations, the 8-bit experts at 4 bits, the
4-bit experts as they are; routers stay float32, as in the mixtral
reference.
"""
from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from archs import deepseek_v2 as W
from benchlib.weights import served

HI = jax.lax.Precision.HIGHEST
CHUNK = 1024        # rows per token-wise call: one compiled shape
Q_BLOCK = 512       # attention query rows per step
EPS = "rms_norm_eps"


def _f32(a):
    return jnp.asarray(a, jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "group"))
def quant_dequant(w, bits: int, group: int):
    """(K, N) weight -> the float32 values of its quantized form:
    symmetric, one absmax scale (kept in bfloat16) per ``group`` rows of
    K, codes in ``[-2**(bits-1), 2**(bits-1) - 1]``."""
    k, n = w.shape
    wf = _f32(w).reshape(k // group, group, n)
    qmax = 2.0 ** (bits - 1) - 1
    scales = jnp.max(jnp.abs(wf), axis=1) / qmax
    inv = jnp.where(scales > 0, 1.0 / scales, 0.0)
    q = jnp.clip(jnp.round(wf * inv[:, None, :]), -qmax - 1, qmax)
    s = scales.astype(jnp.bfloat16).astype(jnp.float32)
    return (q * s[:, None, :]).reshape(k, n)


def _act_int8(x):
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -128, 127) * s


def _mm(x, w, control: bool):
    if control:
        x = _act_int8(x)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(c: dict) -> np.ndarray:
    """The rotary's inverse frequencies, as the published
    ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    y = c.get("rope_scaling")
    if not y:
        return freq_extra
    factor, orig = float(y["factor"]), y["original_max_position_embeddings"]
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr_dim(y["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def rotary_mscale(c: dict) -> float:
    y = c.get("rope_scaling")
    if not y:
        return 1.0
    return _mscale(y["factor"], y["mscale"]) \
        / _mscale(y["factor"], y["mscale_all_dim"])


def softmax_scale(c: dict) -> float:
    y = c.get("rope_scaling") or {}
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    if y.get("mscale_all_dim"):
        scale *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _rope(x, pos, inv_freq, m):
    """x (S, H, d): interleaved pairs turned by pos * inv_freq."""
    ang = pos[:, None].astype(jnp.float32) * inv_freq
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    xr = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x1, x2 = xr[..., 0], xr[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("eps", "control", "rank"))
def _qkv(x, scale, kv_scale, wq, wkv_a, wkv_b, eps: float, control: bool,
         rank: int):
    """The query, the no-position key and value of every head, and the
    rotary key, before rotary."""
    h = _rms(x, scale, eps)
    kv = _mm(h, wkv_a, control)
    c = _rms(kv[:, :rank], kv_scale, eps)
    return _mm(h, wq, control), _mm(c, wkv_b, control), kv[:, rank:]


@functools.partial(jax.jit, static_argnames=(
    "sp", "heads", "nope", "vd", "m", "scale"), donate_argnums=(0,))
def _attend_into(o, q, kvb, kr, inv_freq, start, n, sp: int, heads: int,
                 nope: int, vd: int, m: float, scale: float):
    """Causal latent attention, expanded, of the sequence at rows
    ``[start, start + n)`` of the packed projections, taken as ``sp``
    rows; its ``n`` rows of output go into ``o``."""
    take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, sp)
    pos = jnp.arange(sp)
    qs = take(q).reshape(sp, heads, -1)
    q_nope, q_rope = qs[..., :nope], _rope(qs[..., nope:], pos, inv_freq, m)
    kv = take(kvb).reshape(sp, heads, nope + vd)
    k_rope = _rope(take(kr)[:, None, :], pos, inv_freq, m)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope, (sp, heads, k_rope.shape[-1]))], -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    v = kv[..., nope:]
    qb = qf.reshape(sp // Q_BLOCK, Q_BLOCK, heads, -1)

    def block(args):
        i, qq = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qq, k, precision=HI) * scale
        ok = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < n)
        sc = jnp.where(ok[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    a = jax.lax.map(block, (jnp.arange(sp // Q_BLOCK), qb))
    a = a.reshape(sp, heads * vd)
    rows = (pos < n)[:, None]
    return jax.lax.dynamic_update_slice_in_dim(
        o, jnp.where(rows, a, take(o)), start, 0)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _post_attn(x, o, wo, scale, eps: float, control: bool):
    x = x + _mm(o, wo, control)
    return x, _rms(x, scale, eps)


@functools.partial(jax.jit, static_argnames=("control",))
def _mlp(h, wg, wu, wd, control: bool):
    return _mm(jax.nn.silu(_mm(h, wg, control)) * _mm(h, wu, control), wd,
               control)


@functools.partial(jax.jit, static_argnames=("top_k", "norm"))
def _route(h, router, factor, top_k: int, norm: bool):
    logit = jnp.matmul(h, router, precision=HI)
    probs = jax.nn.softmax(logit, axis=-1)
    top_p, ids = jax.lax.top_k(probs, top_k)
    if norm:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    srt = jnp.sort(logit, axis=-1)
    return top_p * factor, ids, srt[:, -top_k] - srt[:, -top_k - 1]


@functools.partial(jax.jit, static_argnames=("control",), donate_argnums=(0,))
def _expert_rows(out, h, idx, wts, wg, wu, wd, control: bool):
    """out[idx] += wts * SwiGLU(h[idx]) for one chunk of routed rows
    (padding rows carry weight 0)."""
    xr = h[idx]
    y = jax.nn.silu(_mm(xr, wg, control)) * _mm(xr, wu, control)
    return out.at[idx].add(wts[:, None] * _mm(y, wd, control))


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "control"))
def _logits(x, scale, table, eps: float, vocab: int, control: bool):
    h = _rms(x, scale, eps)
    return _mm(h, table[:vocab].T, control)


def _weight(c, seed, name, layer, expert, bits, control):
    """A weight as float32 at the precision the run computes it in."""
    w = W.leaf_slice(c, seed, name, layer, expert)
    group = c["plan"]["group_size"]
    if control:
        bits = {16: 8, 8: 4, 4: 4}[bits]
    return _f32(w) if bits == 16 else quant_dequant(w, bits, group)


def _attention(c, seed, stack, layer, x, starts, lens, control):
    """One layer's attention block, residual included."""
    eps = float(served(c, EPS))
    nh, nope = c["num_attention_heads"], c["qk_nope_head_dim"]
    vd, r = c["v_head_dim"], c["kv_lora_rank"]
    w = {n: _weight(c, seed, f"{stack}/attn/{n}", layer, 0, 16, control)
         for n in ("wq", "wkv_a", "wkv_b", "wo")}
    norm = _f32(W.leaf_slice(c, seed, f"{stack}/attn_norm/scale", layer))
    kv_norm = _f32(W.leaf_slice(c, seed, f"{stack}/attn/kv_norm/scale",
                                layer))
    q, kvb, kr = _qkv(x, norm, kv_norm, w["wq"], w["wkv_a"], w["wkv_b"],
                      eps=eps, control=control, rank=r)
    inv_freq = jnp.asarray(yarn_inv_freq(c), jnp.float32)
    o = jnp.zeros((x.shape[0], nh * vd), jnp.float32)
    for s0, n in zip(starts, lens):
        o = _attend_into(o, q, kvb, kr, inv_freq, jnp.int32(s0),
                         jnp.int32(n), sp=-(-n // Q_BLOCK) * Q_BLOCK,
                         heads=nh, nope=nope, vd=vd, m=rotary_mscale(c),
                         scale=softmax_scale(c))
    return o, w["wo"]


def _hidden(c: dict, seed: int, tokens: np.ndarray, starts: List[int],
            lens: List[int], control: bool):
    """Final hidden states (before the last norm) of the packed tokens,
    and each token's routing margin: over the expert layers, the least
    gap between the last router logit it is sent by and the first it is
    not."""
    eps = float(served(c, EPS))
    mult = float(served(c, "embedding_multiplier"))
    top_k, ne = c["num_experts_per_tok"], c["n_routed_experts"]
    t = len(tokens)
    # room after the last token for a whole attention block of rows
    tp = -(-t // CHUNK) * CHUNK + -(-max(lens) // Q_BLOCK) * Q_BLOCK
    tok = np.zeros(tp, np.int32)
    tok[:t] = tokens
    table = W.leaf_slice(c, seed, "embed/table")
    x = _f32(jnp.take(table, jnp.asarray(tok), axis=0)) * mult
    del table
    bits = np.asarray(c["plan"]["bits"])
    margins = None
    layers = [("dense_layers", i) for i in range(c["first_k_dense_replace"])]
    layers += [("layers", i) for i in range(W.moe_layers(c))]
    for stack, layer in layers:
        o, wo = _attention(c, seed, stack, layer, x, starts, lens, control)
        ffn_norm = _f32(W.leaf_slice(c, seed, f"{stack}/ffn_norm/scale",
                                     layer))
        x, h = _post_attn(x, o, wo, ffn_norm, eps=eps, control=control)
        del o, wo
        mlp = "mlp" if stack == "dense_layers" else "shared_mlp"
        wm = [_weight(c, seed, f"{stack}/{mlp}/{n}", layer, 0, 16, control)
              for n in ("w_gate", "w_up", "w_down")]
        out = _mlp(h, *wm, control=control)
        del wm
        if stack == "layers":
            router = _f32(W.leaf_slice(c, seed, "layers/moe/router", layer))
            wts, ids, margin = _route(
                h, router, float(c["routed_scaling_factor"]), top_k=top_k,
                norm=bool(c["norm_topk_prob"]))
            margins = margin if margins is None \
                else jnp.minimum(margins, margin)
            ids_np, wts_np = np.asarray(ids[:t]), np.asarray(wts[:t])
            for e in range(ne):
                rows, slot = np.nonzero(ids_np == e)
                if not len(rows):
                    continue
                we = [_weight(c, seed, f"layers/moe/{n}", layer, e,
                              int(bits[layer, e]), control)
                      for n in ("w_gate", "w_up", "w_down")]
                w_rows = wts_np[rows, slot]
                for i in range(0, len(rows), CHUNK):
                    idx = np.zeros(CHUNK, np.int32)
                    wr = np.zeros(CHUNK, np.float32)
                    m = min(CHUNK, len(rows) - i)
                    idx[:m], wr[:m] = rows[i:i + m], w_rows[i:i + m]
                    out = _expert_rows(out, h, jnp.asarray(idx),
                                       jnp.asarray(wr), *we, control=control)
                del we
        x = x + out
    return x, np.asarray(margins)


def served_gaps(c: dict, seed: int,
                seqs: Sequence[Tuple[np.ndarray, Sequence[int]]],
                control: bool = False) -> dict:
    """For each sequence (prompt, served tokens) and each served token:
    how far the reference's logit of the served token lies below the
    reference's largest logit at that position. Returns the gaps, in
    order (``gaps``), the reference's routing margin of the position each
    was scored at (``route_margins``), and with ``control`` the gaps of
    the tokens the lower-precision control puts first
    (``control_gaps``)."""
    tokens, starts, lens, scored, served_ids = [], [], [], [], []
    off = 0
    for prompt, out in seqs:
        inp = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(out[:-1], np.int32)])
        starts.append(off)
        lens.append(len(inp))
        p = len(prompt)
        scored.extend(range(off + p - 1, off + p - 1 + len(out)))
        served_ids.extend(int(t) for t in out)
        tokens.append(inp)
        off += len(inp)
    tokens = np.concatenate(tokens)
    scored = np.asarray(scored)
    served_ids = np.asarray(served_ids)
    eps, vocab = float(served(c, EPS)), int(c["vocab_size"])
    final = _f32(W.leaf_slice(c, seed, "final_norm/scale"))
    table = W.leaf_slice(c, seed, "lm_head/table")
    with jax.default_matmul_precision("highest"):
        xr, margins = _hidden(c, seed, tokens, starts, lens, control=False)
        xc = _hidden(c, seed, tokens, starts, lens, True)[0] if control \
            else None
        t_ref = _f32(table)
        t_ctl = quant_dequant(table.T, 8, c["plan"]["group_size"]).T \
            if control else None
        gaps, gaps_c = [], []
        for i in range(0, len(scored), CHUNK):
            pos = np.zeros(CHUNK, np.int32)
            m = min(CHUNK, len(scored) - i)
            pos[:m] = scored[i:i + m]
            lr = np.asarray(_logits(xr[pos], final, t_ref, eps=eps,
                                    vocab=vocab, control=False))[:m]
            best = lr.max(-1)
            gaps.append(best - lr[np.arange(m), served_ids[i:i + m]])
            if control:
                lc = np.asarray(_logits(xc[pos], final, t_ctl, eps=eps,
                                        vocab=vocab, control=True))[:m]
                gaps_c.append(best - lr[np.arange(m), lc.argmax(-1)])
    res = {"gaps": np.concatenate(gaps), "route_margins": margins[scored]}
    if control:
        res["control_gaps"] = np.concatenate(gaps_c)
    return res
