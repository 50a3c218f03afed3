"""Mixed-precision MoE layer: N expert banks (one per ladder rung, e.g.
int4 | int8 | bf16) + explicit expert-parallel dispatch under shard_map.

The paper's partial expert quantization turns each MoE layer into per-rung
banks — ``q4`` (packed int4 + scales), ``q8`` (int8 + scales) and ``f16``
(bf16) — in ASCENDING-bits bank order, with a per-layer expert permutation
mapping routed ids into bank slots (``PrecisionPlan.expert_order``). Bank
sizes are static per plan — one recompile per ladder-rung-count signature
(``PrecisionPlan.bank_sizes()``), placement changes are graph-free. The
binary ladder degenerates to the historical dual-bank ``[q4 | f16]``
layout bit-for-bit (DESIGN.md §11).

Dispatch (DESIGN.md §4) runs under shard_map over (dp..., model):

  * routing (tiny matmul) happens at jit level, sharded over dp;
  * **EP** (num_experts >= model-axis size, e.g. Kimi 384e/16): experts are
    sharded over ``model``; every rank selects the assignments that hit its
    local experts, packs them into a capacity-bounded (E_loc, C, d) buffer
    (sort + scatter — all local ops), runs the dual-bank FFN, scatters back
    weighted outputs, and one psum over ``model`` combines the per-rank
    contributions. Activations stay replicated over ``model``;
  * **TP** (num_experts < model-axis size, e.g. Mixtral 8e/16): every rank
    holds all experts on a 1/16 slice of d_ff; same local dispatch with all
    experts local; the identical psum now reduces partial down-projections.

Both paths cost exactly one (T_loc, d) all-reduce per MoE layer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.core.quantization import QTensor, dequantize, quantize


# --------------------------------------------------------------------------
# Routing (jit level)
# --------------------------------------------------------------------------

_TRACE = __import__("threading").local()


class capture_routing:
    """Collect concrete routing ids from eager (unjitted) forwards —
    benchmarks/cache_sim.py uses this to test the paper's uniform-access
    assumption on a *trained* router."""

    def __enter__(self):
        _TRACE.ids = []
        return _TRACE.ids

    def __exit__(self, *exc):
        _TRACE.ids = None


class capture_moe_inputs:
    """Collect each MoE layer's router inputs from eager forwards: one
    ``(x (T,d) f32, probs (T,E) f32)`` pair per layer, in layer order.
    The sensitivity calibration pass (core/sensitivity.py, DESIGN.md
    §15) replays the captured tokens through each expert's FFN at every
    ladder rung to measure activation-weighted quantization error."""

    def __enter__(self):
        _TRACE.moe = []
        return _TRACE.moe

    def __exit__(self, *exc):
        _TRACE.moe = None


def route(router_w: jax.Array, x: jax.Array, moe: MoEConfig, *,
          train: bool) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """x: (T, d) -> (weights (T,k) f32, ids (T,k) i32, aux losses)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, ids = jax.lax.top_k(probs, moe.top_k)
    trace = getattr(_TRACE, "ids", None)
    if trace is not None and not isinstance(ids, jax.core.Tracer):
        trace.append(np.asarray(ids))
    moe_trace = getattr(_TRACE, "moe", None)
    if moe_trace is not None and not isinstance(probs, jax.core.Tracer):
        moe_trace.append((np.asarray(x, np.float32),
                          np.asarray(probs, np.float32)))
    weights = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    aux: Dict[str, jax.Array] = {}
    if train:
        e = moe.num_experts
        # Switch-style load-balance: E * sum_e f_e * P_e
        dispatch = jax.nn.one_hot(ids, e, dtype=jnp.float32).sum(1)  # (T,E)
        f_e = dispatch.mean(0)
        p_e = probs.mean(0)
        aux["load_balance"] = moe.load_balance_loss * e * jnp.sum(f_e * p_e)
        lse = jax.nn.logsumexp(logits, axis=-1)
        aux["router_z"] = moe.router_z_loss * jnp.mean(lse ** 2)
    return weights, ids, aux


# --------------------------------------------------------------------------
# Local dispatch (inside shard_map): sort -> capacity scatter -> FFN ->
# weighted combine. Everything here is per-device.
# --------------------------------------------------------------------------

def _bank_bits(name: str) -> int:
    """Bank key -> bit-width: 'f16' -> 16, 'qN' -> N."""
    return 16 if name == "f16" else int(name[1:])


def _bank_name(bits: int) -> str:
    return "f16" if bits >= 16 else f"q{bits}"


def bank_keys(banks) -> list:
    """Non-empty bank keys in ascending-bits BANK ORDER (the expert
    storage order: cheapest rung first — binary: ['q4', 'f16'])."""
    return sorted((k for k in banks if banks.get(k) is not None),
                  key=_bank_bits)


def _local_slot(flat_e, *, rank, totals, locs):
    """Map global (permuted) expert ids to this rank's local bank slots.

    ``totals``/``locs`` are per-bank global/per-rank expert counts in
    bank order. Each bank is sharded over the EP axis independently:
    within bank b (global offset O_b), rank r owns experts
    [O_b + r*loc_b, O_b + (r+1)*loc_b) -> local slots
    [sum(loc_<b), sum(loc_<b) + loc_b). Returns (slot, is_local)."""
    slot = jnp.zeros_like(flat_e)
    ok = jnp.zeros(flat_e.shape, bool)
    g_off = l_off = 0
    for tot, loc in zip(totals, locs):
        rel = flat_e - g_off - rank * loc
        in_bank = (flat_e >= g_off) & (flat_e < g_off + tot)
        bank_ok = in_bank & (rel >= 0) & (rel < loc)
        slot = jnp.where(bank_ok, l_off + rel, slot)
        ok = ok | bank_ok
        g_off += tot
        l_off += loc
    return slot, ok


def _dispatch_local(x, ids, weights, *, rank, totals, locs, capacity):
    """Pack routed tokens into (e_loc, capacity, d); returns buffers +
    metadata needed for the combine."""
    t, d = x.shape
    e_loc = sum(locs)
    k = ids.shape[1]
    flat_e = ids.reshape(-1)                                  # (T*k,)
    flat_w = weights.reshape(-1)
    local_e, is_local = _local_slot(flat_e, rank=rank, totals=totals,
                                    locs=locs)
    key = jnp.where(is_local, local_e, e_loc)
    order = jnp.argsort(key, stable=True)                     # (T*k,)
    sorted_e = key[order]
    counts = jnp.bincount(sorted_e, length=e_loc + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)])[:-1]
    pos = jnp.arange(t * k) - starts[sorted_e]
    valid = (sorted_e < e_loc) & (pos < capacity)
    dest = jnp.where(valid, sorted_e * capacity + pos, e_loc * capacity)
    tok = order // k
    xbuf = jnp.zeros((e_loc * capacity, d), x.dtype)
    xbuf = xbuf.at[dest].set(x[tok], mode="drop")
    return xbuf.reshape(e_loc, capacity, d), dest, tok, flat_w[order]


def _combine_local(ybuf, dest, tok, w_sorted, t, d):
    """Each token's routed, weighted expert outputs summed in f32 (the
    caller rounds once, after the EP all-reduce)."""
    flat = ybuf.reshape(-1, ybuf.shape[-1])
    contrib = jnp.take(flat, dest, axis=0, mode="fill", fill_value=0)
    contrib = contrib * w_sorted[:, None].astype(contrib.dtype)
    return jnp.zeros((t, d), jnp.float32).at[tok].add(
        contrib.astype(jnp.float32))


# --------------------------------------------------------------------------
# N-bank expert FFN (one bank per ladder rung, ascending-bits order)
# --------------------------------------------------------------------------

def _ffn_bf16(bank, xb, act, use_kernel: bool = False):
    """(E, C, d) x (E, d, f) -> (E, C, d).

    ``use_kernel=True`` routes through the grouped bf16 Pallas kernel
    (one launch for the whole f16 bank — DESIGN.md §13); numerics are
    allclose to the einsum (f32 accumulation either way), not bitwise."""
    if use_kernel:
        from repro.kernels.ops import grouped_bf16_matmul
        mm = grouped_bf16_matmul
    else:
        mm = functools.partial(jnp.einsum, "ecd,edf->ecf")
    up = mm(xb, bank["w_up"])
    if act == "swiglu":
        h = jax.nn.silu(mm(xb, bank["w_gate"])) * up
    elif act == "gelu":
        h = jax.nn.gelu(up, approximate=True)
    else:
        h = jnp.square(jax.nn.relu(up))
    return mm(h, bank["w_down"])


def _ffn_q(bank, xb, act, use_kernel: bool):
    """Quantized bank: fused Pallas kernel (serving on a TPU) or the jnp
    dequantize-then-einsum reference the kernels are checked against."""
    if use_kernel:
        from repro.kernels.ops import q_expert_matmul
        up = q_expert_matmul(xb, bank["w_up"])
        if act == "swiglu":
            h = jax.nn.silu(q_expert_matmul(xb, bank["w_gate"])) * up
        elif act == "gelu":
            h = jax.nn.gelu(up, approximate=True)
        else:
            h = jnp.square(jax.nn.relu(up))
        return q_expert_matmul(h, bank["w_down"])
    deq = {k: dequantize(v) for k, v in bank.items()}
    return _ffn_bf16(deq, xb, act)


def _expert_ffn(banks, xb, act, use_kernel):
    """banks: {"q4"|"q8": {...QTensor...}|None, "f16": {...bf16...}|None}
    with expert storage in ascending-bits bank order along E (quantized
    rungs first); ``xb`` is sliced per bank accordingly.

    With ``use_kernel`` each rung's whole bank is ONE grouped kernel
    launch (expert-group grid axis, dequant in VMEM — DESIGN.md §13), so
    the decode FFN dispatches n_rungs kernels regardless of expert count
    instead of one per expert."""
    outs = []
    off = 0
    for key in bank_keys(banks):
        bank = banks[key]
        n = bank["w_up"].shape[0]
        if not n:
            continue
        sl = xb[off:off + n]
        if _bank_bits(key) < 16:
            outs.append(_ffn_q(bank, sl, act, use_kernel))
        else:
            outs.append(_ffn_bf16(bank, sl, act, use_kernel))
        off += n
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


# --------------------------------------------------------------------------
# The shard_map'd MoE apply
# --------------------------------------------------------------------------

# Token-gather pays only while the gathered activations stay ~cache-scale;
# above this the dispatch-buffer amplification dominates (see moe_apply).
TOKEN_GATHER_MAX_BYTES = 64 << 20


@dataclasses.dataclass(frozen=True)
class MoEParallelism:
    mesh: Any                      # jax Mesh
    dp_axes: Tuple[str, ...]       # token axes ("pod","data") / ("data",)
    ep_axis: str = "model"
    # Second weight-sharding axis for EP banks (ZeRO/FSDP dimension): the
    # d_ff dim of every expert is sharded over it. Token-gather dispatch
    # (below) keeps the weights fully sharded and moves ACTIVATIONS over
    # this axis instead — 1T-scale experts never cross the wire.
    fsdp_axis: Optional[str] = None

    @property
    def ep_size(self) -> int:
        return self.mesh.shape[self.ep_axis]

    @property
    def fsdp_size(self) -> int:
        if self.fsdp_axis is None or self.fsdp_axis not in self.mesh.shape:
            return 1
        return self.mesh.shape[self.fsdp_axis]


def _fsdp_active(banks, moe: MoEConfig, par: MoEParallelism, ep: bool):
    """Token-gather EP applies when experts are also d_ff-sharded over the
    fsdp axis (kimi-1T: (E/16 on model) x (f/16 on data) per device)."""
    if not ep or par.fsdp_size <= 1:
        return False
    fs = par.fsdp_size

    def ok(leaf_shape, fdim):
        return leaf_shape[fdim] % fs == 0

    for key in bank_keys(banks):
        b = banks[key]
        for name, w in b.items():
            arr = w.q if isinstance(w, QTensor) else w
            fdim = 1 if name == "w_down" else 2
            if not ok(arr.shape, fdim):
                return False
            if isinstance(w, QTensor) and w.scales.shape[fdim] % fs:
                return False
    return True


def _bank_specs(banks, moe: MoEConfig, par: MoEParallelism,
                fsdp: bool = False):
    """PartitionSpecs for the bank pytree: EP shards the leading E dim
    (+ d_ff over the fsdp axis in token-gather mode), TP shards the d_ff
    dim (dim 2 for up/gate & their scales, dim 1 for down & its scales)."""
    ep = moe.num_experts >= par.ep_size
    fx = par.fsdp_axis if fsdp else None

    def spec_for(path, leaf):
        if ep:
            is_down = "w_down" in path
            return P(par.ep_axis, fx, None) if is_down \
                else P(par.ep_axis, None, fx)
        is_down = "w_down" in path
        return P(None, par.ep_axis, None) if is_down \
            else P(None, None, par.ep_axis)

    def walk(tree, path=""):
        if isinstance(tree, QTensor):
            return QTensor(q=spec_for(path, tree.q),
                           scales=spec_for(path, tree.scales),
                           bits=tree.bits, group_size=tree.group_size)
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
        if tree is None:
            return None
        return spec_for(path, tree)

    return walk(banks), ep


def serve_shardings(serve_params, moe: MoEConfig, mesh):
    """Shardings that place serve-layout params on an EP serving mesh
    (``launch.mesh.make_ep_mesh``) as the step reads them: each
    layer-stacked rung bank split like ``moe_apply``'s shard_map
    in_specs, every other leaf replicated. Placed once per replan, the
    step then moves no weights."""
    par = MoEParallelism(mesh=mesh, dp_axes=("data",))
    specs, _ = _bank_specs(serve_params["layers"]["moe"]["banks"], moe, par)
    rep = jax.sharding.NamedSharding(mesh, P())
    out = jax.tree_util.tree_map(lambda _: rep, serve_params)
    out["layers"]["moe"]["banks"] = jax.tree_util.tree_map(
        lambda s: jax.sharding.NamedSharding(mesh, P(None, *s)), specs)
    return out


def moe_apply(banks, x: jax.Array, weights: jax.Array, ids: jax.Array,
              moe: MoEConfig, par: MoEParallelism, *, act: str = "swiglu",
              use_kernel: bool = False,
              capacity: Optional[int] = None) -> jax.Array:
    """x: (T, d) sharded over dp_axes; returns (T, d) same sharding.

    ``banks`` is either the train layout {"f16": {...(E,d,f) bf16...}} or
    the rung-keyed serve layout {"q4": ..., "q8": ..., "f16": ...}
    (bank order = ascending bits, cheapest rung first).

    ``capacity`` overrides the capacity-factor formula with an explicit
    per-expert slot count (callers that must be drop-free — e.g. the
    speculative verify forward, DESIGN.md §17 — pass ``>= T`` so no
    routed assignment can be displaced).
    """
    t, d = x.shape
    ep = moe.num_experts >= par.ep_size
    fsdp = _fsdp_active(banks, moe, par, ep)
    if fsdp:
        # Regime split (§Perf kimi iterations 1-2): token-gather wins when
        # the gathered token set is small (decode: MBs vs the layer's GBs
        # of expert weights — measured 257x less wire). At train/prefill
        # token counts the gathered-x + amplified dispatch buffers blow
        # HBM (measured: kimi prefill peak 45 -> 322 GiB), so the weights
        # are gathered once per layer instead (ZeRO-3) and amortized over
        # the whole microbatch.
        n_dp_pre = int(np.prod([par.mesh.shape[a] for a in par.dp_axes]))
        t_disp_pre = (t // n_dp_pre) * par.fsdp_size
        fsdp = t_disp_pre * d * 2 <= TOKEN_GATHER_MAX_BYTES
    bank_specs, _ = _bank_specs(banks, moe, par, fsdp=fsdp)
    lead = par.dp_axes if len(par.dp_axes) > 1 else \
        (par.dp_axes[0] if par.dp_axes else None)
    dp = P(lead, None)
    n_dp = int(np.prod([par.mesh.shape[a] for a in par.dp_axes]))
    t_loc = t // n_dp
    keys = bank_keys(banks)
    totals = tuple(banks[k]["w_up"].shape[0] for k in keys)
    shards = par.ep_size if ep else 1
    if any(tot % shards for tot in totals):
        raise ValueError(
            f"EP banks must split evenly: "
            f"{dict(zip(keys, totals))} over {shards} shards "
            f"(planner rounds per-layer counts)")
    locs = tuple(tot // shards for tot in totals)
    # Token-gather mode: the fsdp axis contributes its tokens instead of
    # its weight shards (§Perf 'kimi-decode' iteration: for 1T-scale
    # experts, tokens are ~4 orders of magnitude lighter than weights).
    t_disp = t_loc * (par.fsdp_size if fsdp else 1)
    # static per-shard capacity (tokens replicated over model: each rank
    # sees all dispatched assignments, keeps only its local experts' share)
    if capacity is None:
        cap = int(np.ceil(t_disp * moe.top_k * moe.capacity_factor
                          / moe.num_experts))
    else:
        cap = int(capacity)
    cap = max(4, ((cap + 3) // 4) * 4)

    def local_fn(banks_l, x_l, w_l, ids_l):
        rank = jax.lax.axis_index(par.ep_axis) if ep else 0
        if fsdp:
            # tokens in, weights stationary: gather the fsdp axis's token
            # shards; every rank computes its (E_loc x f_loc) weight slice
            # for ALL gathered tokens.
            x_l = jax.lax.all_gather(x_l, par.fsdp_axis, axis=0, tiled=True)
            w_l = jax.lax.all_gather(w_l, par.fsdp_axis, axis=0, tiled=True)
            ids_l = jax.lax.all_gather(ids_l, par.fsdp_axis, axis=0,
                                       tiled=True)
        xbuf, dest, tok, w_sorted = _dispatch_local(
            x_l, ids_l, w_l, rank=rank, totals=totals, locs=locs,
            capacity=cap)
        # the expert FFN is shape-polymorphic in f: gate/up/silu are
        # elementwise on this rank's f-slice, w_down yields partial sums
        ybuf = _expert_ffn(banks_l, xbuf, act, use_kernel)
        y = _combine_local(ybuf, dest, tok, w_sorted, t_disp, d)
        if fsdp:
            # partial over d_ff shards AND scattered back to this rank's
            # token shard in one collective
            y = jax.lax.psum_scatter(y, par.fsdp_axis, scatter_dimension=0,
                                     tiled=True)
        # a token's experts may sit on different ranks: the all-reduce
        # sums in f32 and the sum is rounded once, whatever the ep (a
        # bf16 all-reduce on the chip rounded otherwise than the
        # single-device scatter-add: ep=4 and ep=1 differed by a ulp)
        return jax.lax.psum(y, par.ep_axis).astype(ybuf.dtype)

    fn = jax.shard_map(
        local_fn,
        mesh=par.mesh,
        in_specs=(bank_specs, dp, dp, dp),
        out_specs=dp,
        check_vma=False,
    )
    return fn(banks, x, weights, ids)


# --------------------------------------------------------------------------
# Bank construction from a PrecisionPlan (serve) or plain params (train)
# --------------------------------------------------------------------------

def train_banks(moe_params: Dict[str, jax.Array]) -> Dict[str, Any]:
    return {"q4": None,
            "f16": {k: moe_params[k] for k in ("w_gate", "w_up", "w_down")}}


def build_ladder_banks(moe_params: Dict[str, jax.Array], bits_row,
                       *, ladder=(16, 4), group_size: int = 64):
    """Split one layer's experts into per-rung banks in ascending-bits
    bank order (DESIGN.md §11).

    ``bits_row``: (E,) int — each expert's ladder rung. Returns
    (banks, order) where ``order`` is the expert permutation (cheapest
    rung first) — the caller permutes the router columns with it. Every
    ladder rung gets a bank key (``None`` when empty) so per-layer bank
    pytrees stack cleanly across a balanced plan."""
    bits_row = np.asarray(bits_row)
    banks: Dict[str, Any] = {}
    order = []
    for b in sorted(ladder):
        # gather each bank's experts straight from the layer (no full
        # permuted copy of it: at Mixtral widths a layer is 2.8 GB)
        idx = np.where(bits_row == b)[0]
        order.append(idx)
        name = _bank_name(b)
        if not len(idx):
            banks[name] = None
            continue
        if b >= 16:
            banks[name] = {k: jnp.take(moe_params[k], idx, axis=0)
                           for k in ("w_gate", "w_up", "w_down")}
        else:
            banks[name] = {k: _quantize_experts(
                moe_params[k], jnp.asarray(idx, jnp.int32), b, group_size)
                           for k in ("w_gate", "w_up", "w_down")}
    return banks, np.concatenate(order).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("bits", "group_size"))
def _quantize_experts(w: jax.Array, idx: jax.Array, bits: int,
                      group_size: int) -> QTensor:
    """``quantize(w[idx])``, one expert per loop step: the same bits
    (quantization is per expert), with f32 temporaries of one expert
    instead of a whole bank, which at Mixtral widths are GBs, more than
    one chip has to spare."""
    return jax.lax.map(lambda i: quantize(w[i], bits, group_size), idx)


def build_mixed_banks(moe_params: Dict[str, jax.Array], quant_mask,
                      *, bits: int = 4, group_size: int = 64):
    """Legacy binary spelling of :func:`build_ladder_banks`:
    quant_mask (E,) bool -> [q4 | f16] banks, quantized first."""
    quant_mask = np.asarray(quant_mask).astype(bool)
    bits_row = np.where(quant_mask, bits, 16)
    return build_ladder_banks(moe_params, bits_row, ladder=(16, bits),
                              group_size=group_size)


def moe_dense_ref(moe_params, x, moe: MoEConfig, act: str = "swiglu"):
    """O(T*E) oracle: every expert computes every token (tests only)."""
    weights, ids, _ = route(moe_params["router"], x, moe, train=False)
    w_full = jnp.zeros((x.shape[0], moe.num_experts), jnp.float32)
    w_full = jax.vmap(lambda w, i, row: row.at[i].add(w))(
        weights, ids, w_full)
    banks = {"w_gate": moe_params["w_gate"], "w_up": moe_params["w_up"],
             "w_down": moe_params["w_down"]}
    y_all = _ffn_bf16(banks, jnp.broadcast_to(
        x[None], (moe.num_experts,) + x.shape), act)       # (E, T, d)
    return jnp.einsum("etd,te->td", y_all.astype(jnp.float32), w_full
                      ).astype(x.dtype)
