"""Plain float32 reference of the Mixtral-style decoder the configuration
files describe, for the ``correct`` check. It imports nothing of the
program: weights are drawn again from the seed by ``benchlib.weights``,
and the experts' rungs come from the configuration's pinned plan.

Per layer: RMSNorm (``rms_norm_eps`` as served), grouped-query attention with
rotary embeddings (split halves, ``rope_theta``), causal over the whole
sequence; RMSNorm, a softmax router over ``num_local_experts``, the top
``num_experts_per_tok`` renormalised, and SwiGLU experts; residuals
around both. Token embeddings are scaled by ``embedding_multiplier``
(``program_departures`` states what the program serves with);
logits cover the first ``vocab_size`` rows of the output table. Expert
weights at a quantized rung are quantized as the configuration states:
symmetric, one absmax scale (kept in bfloat16) per ``group_size`` rows
of the reduction dimension, codes in ``[-2**(b-1), 2**(b-1) - 1]``.

``control=True`` computes the same model one precision lower, the step a
later change might take: every bfloat16 matrix product as int8 weights
(same grouping) by int8 activations (one scale per row), the 8-bit
experts at 4 bits, the 4-bit experts as they are.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from archs import mixtral as W

HI = jax.lax.Precision.HIGHEST
CHUNK = 1024        # rows per token-wise call: one compiled shape
Q_BLOCK = 512       # attention query rows per step


def _f32(a):
    return jnp.asarray(a, jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "group"))
def quant_dequant(w, bits: int, group: int):
    """(K, N) weight -> the float32 values of its quantized form."""
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


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _qkv(x, scale, wq, wk, wv, eps: float, control: bool):
    h = _rms(x, scale, eps)
    return _mm(h, wq, control), _mm(h, wk, control), _mm(h, wv, control)


@functools.partial(jax.jit, static_argnames=("sp", "heads", "kv_heads",
                                             "theta"), donate_argnums=(0,))
def _attend_into(o, q, k, v, start, n, sp: int, heads: int, kv_heads: int,
                 theta: float):
    """Causal attention of the sequence at rows ``[start, start + n)`` of
    the packed q/k/v, taken as ``sp`` rows; its ``n`` rows of output go
    into ``o``."""
    take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, sp)
    hd = q.shape[1] // heads
    pos = jnp.arange(sp)
    qs = _rope(take(q).reshape(sp, heads, hd), pos, theta)
    ks = _rope(take(k).reshape(sp, kv_heads, hd), pos, theta)
    vs = take(v).reshape(sp, kv_heads, hd)
    g = heads // kv_heads
    qb = qs.reshape(sp // Q_BLOCK, Q_BLOCK, kv_heads, g, hd)

    def block(args):
        i, qq = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qcgd,kcd->cgqk", qq, ks, precision=HI) * hd ** -0.5
        ok = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < n)
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("cgqk,kcd->qcgd", p, vs, precision=HI)

    a = jax.lax.map(block, (jnp.arange(sp // Q_BLOCK), qb))
    a = a.reshape(sp, heads * hd)
    rows = (pos < n)[:, None]
    return jax.lax.dynamic_update_slice_in_dim(
        o, jnp.where(rows, a, take(o)), start, 0)


@functools.partial(jax.jit, static_argnames=("eps", "control", "top_k"))
def _post_attn(x, o, wo, scale, router, eps: float, control: bool,
               top_k: int):
    x = x + _mm(o, wo, control)
    h = _rms(x, scale, eps)
    probs = jax.nn.softmax(jnp.matmul(h, router, precision=HI), axis=-1)
    top_p, ids = jax.lax.top_k(probs, top_k)
    logit = jnp.sort(jnp.matmul(h, router, precision=HI), axis=-1)
    margin = logit[:, -top_k] - logit[:, -top_k - 1]
    return x, h, top_p / top_p.sum(-1, keepdims=True), ids, margin


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


def _hidden(c: dict, seed: int, tokens: np.ndarray, starts: List[int],
            lens: List[int], control: bool):
    """Final hidden states (before the last norm) of the packed tokens,
    and each token's routing margin: over the layers, the least gap
    between the last router logit it is sent by and the first it is
    not."""
    eps = float(W.served(c, "rms_norm_eps"))
    mult = float(W.served(c, "embedding_multiplier"))
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    top_k, ne = c["num_experts_per_tok"], c["num_local_experts"]
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
    for layer in range(c["num_hidden_layers"]):
        lw = {n: _f32(W.leaf_slice(c, seed, f"layers/{n}", layer))
              for n in ("attn_norm/scale", "ffn_norm/scale", "moe/router")}
        for n in ("wq", "wk", "wv", "wo"):
            lw[n] = _weight(c, seed, f"layers/attn/{n}", layer, 0, 16,
                            control)
        q, k, v = _qkv(x, lw["attn_norm/scale"], lw["wq"], lw["wk"],
                       lw["wv"], eps=eps, control=control)
        o = jnp.zeros_like(q)
        for s0, n in zip(starts, lens):
            o = _attend_into(o, q, k, v, jnp.int32(s0), jnp.int32(n),
                             sp=-(-n // Q_BLOCK) * Q_BLOCK, heads=nh,
                             kv_heads=nkv, theta=float(c["rope_theta"]))
        x, h, wts, ids, margin = _post_attn(
            x, o, lw["wo"], lw["ffn_norm/scale"], lw["moe/router"], eps=eps,
            control=control, top_k=top_k)
        del q, k, v, o
        margins = margin if margins is None else jnp.minimum(margins, margin)
        ids_np, wts_np = np.asarray(ids[:t]), np.asarray(wts[:t])
        out = jnp.zeros_like(x)
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
    tokens, starts, lens, scored, served = [], [], [], [], []
    off = 0
    for prompt, out in seqs:
        inp = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(out[:-1], np.int32)])
        starts.append(off)
        lens.append(len(inp))
        p = len(prompt)
        scored.extend(range(off + p - 1, off + p - 1 + len(out)))
        served.extend(int(t) for t in out)
        tokens.append(inp)
        off += len(inp)
    tokens = np.concatenate(tokens)
    scored = np.asarray(scored)
    served = np.asarray(served)
    eps, vocab = float(W.served(c, "rms_norm_eps")), int(c["vocab_size"])
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
            gaps.append(best - lr[np.arange(m), served[i:i + m]])
            if control:
                lc = np.asarray(_logits(xc[pos], final, t_ctl, eps=eps,
                                        vocab=vocab, control=True))[:m]
                gaps_c.append(best - lr[np.arange(m), lc.argmax(-1)])
    res = {"gaps": np.concatenate(gaps), "route_margins": margins[scored]}
    if control:
        res["control_gaps"] = np.concatenate(gaps_c)
    return res
