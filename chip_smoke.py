#!/usr/bin/env python3
"""Chip smoke test: serve Mixtral-8x7B at its published widths on one
TPU through the adaptive engine, and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # only the ep=4 vs ep=1 phase

Weights are random, drawn from ``--seed``; the one cut is depth
(``num_layers=2``). The engine serves through its normal entry points
(``build_engine``, ``QoSController.set_target``, ``submit_request``,
``run_iteration``) at a target whose frontier point puts experts on all
three rungs of the (16, 8, 4) ladder and some on the host tier, so the
expert cache stages host-tier experts with ``device_put``. Each check
fails the run:

* the engine serves through the Pallas kernels, and the compiled decode
  step (the served model function, on the served banks, at the engine's
  slot count) holds a ``tpu_custom_call`` for every rung of the plan;
* one prefill and one decode step match the jnp dequant oracle
  (``use_kernel=False``) on the same banks, within the tolerance of
  tests/test_kernels.py;
* every request returns its tokens, inside the vocabulary, and at least
  one host-tier expert was staged through the expert cache.

``--four-chips`` runs instead: an ep=4 engine on ``make_ep_mesh(4)`` and
the ep=1 engine on device 0 at the same plan; the same logits check
between them, through the kernels and through the oracle, and of layer
0's MoE alone; the kernel check on the ep=4 step; and the greedy tokens
both serve.

Times printed are smoke timings of one run, not benchmark numbers. The
last line of stdout is ``{"ok": true, "device": {...}}``. With no TPU
(for example under ``JAX_PLATFORMS=cpu``) the script exits non-zero
before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import mixed_moe  # noqa: E402
from repro.core.pareto import QoSTarget  # noqa: E402
from repro.core.precision_plan import HOST  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serving.api import (EngineConfig, ServeRequest,  # noqa: E402
                               build_engine)
from repro.serving.ep import build_ep_engine  # noqa: E402
from repro.serving.qos import QoSController  # noqa: E402

ARCH = "mixtral-8x7b"
NUM_LAYERS = 2                 # the one cut: depth (published: 32)
LADDER = (16, 8, 4)
PROMPT_LEN = 16
NEW_TOKENS = 16
REQUESTS = 4
SLOTS = 4
#: tests/test_kernels.py: rtol 5e-2, atol 5e-2 * max|reference|
RTOL = ATOL = 5e-2
#: Pallas kernel name per rung, as it appears in the compiled HLO
KERNEL_OF_RUNG = {4: "grouped_q4_matmul", 8: "grouped_q8_matmul",
                  16: "grouped_bf16_matmul"}
#: the four-chip plan: 8 q8 + 8 q4 experts (4 + 4 per layer, which
#: divides over ep=4; three rungs cannot, with 8 experts per layer)
FOUR_CHIP_COUNTS = (0, 8, 8)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def smoke_config():
    cfg = get_config(ARCH)
    return cfg.replace(num_layers=NUM_LAYERS,
                       mop=dataclasses.replace(cfg.mop, ladder=LADDER))


def engine_config(cfg, **kw) -> EngineConfig:
    return EngineConfig(max_slots=SLOTS, max_len=PROMPT_LEN + NEW_TOKENS,
                        ladder=cfg.mop.ladder, **kw)


def prompts(cfg, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
            for _ in range(REQUESTS)]


def mixed_host_target(frontier) -> QoSTarget:
    """A target that the frontier resolves to a point with experts on
    every rung and some on the host tier (the best-quality such point
    that some target selects)."""
    cands = sorted((p for p in frontier.points
                    if all(c > 0 for c in p.counts_per_rung)
                    and (p.plan.location == HOST).any()),
                   key=lambda p: (p.qos.quality_proxy, p.qos.device_bytes))
    for p in cands:
        target = QoSTarget(
            min_tokens_per_s=p.qos.tokens_per_s,
            max_quality_loss=p.qos.quality_proxy - 1.0 + 1e-9,
            mem_budget_bytes=p.qos.device_bytes)
        if frontier.select(target) is p:
            return target
    raise RuntimeError("no target selects a frontier point with every "
                       "rung and a host tier")


def step_logits(model, params, prompt, *, slots, max_len, page_size):
    """Prefill ``prompt`` into slot 0, then decode its last token once
    more at the next position, with ``model``'s jitted paged steps at
    ``slots`` rows (the engine's own decode shape). Returns (prefill
    logits, decode logits, compiled decode step, compile seconds)."""
    pool, meta = model.init_paged_cache(slots, max_len,
                                        page_size=page_size)
    table = np.zeros((slots, meta.chunks_per_slot), np.int32)
    table[0] = np.arange(1, meta.chunks_per_slot + 1)
    s = len(prompt)
    pre_args = (params, pool, jnp.asarray(table[0]),
                jnp.asarray(prompt[None]),
                jnp.asarray(np.arange(s, dtype=np.int32)[None]),
                jnp.int32(s - 1))
    toks = np.zeros((slots, 1), np.int32)
    pos = np.full((slots,), -1, np.int32)
    toks[0, 0], pos[0] = prompt[-1], s
    dec_args = [params, pool, jnp.asarray(table), jnp.asarray(toks),
                jnp.asarray(pos)]
    t0 = time.perf_counter()
    prefill = jax.jit(functools.partial(
        model.paged_prefill_into_slot, window=meta.window)
    ).lower(*pre_args).compile()
    decode = jax.jit(functools.partial(
        model.paged_decode_step_routed, window=meta.window)
    ).lower(*dec_args).compile()
    compile_s = time.perf_counter() - t0
    pre_logits, dec_args[1] = prefill(*pre_args)
    dec_logits = decode(*dec_args)[0]
    return (np.asarray(pre_logits[0], np.float32),
            np.asarray(dec_logits[0], np.float32), decode, compile_s)


def assert_close(what: str, got: np.ndarray, ref: np.ndarray) -> None:
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise AssertionError(f"{what}: non-finite logits")
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    log(f"{what}: bit-identical {got.tobytes() == ref.tobytes()}; "
        f"max|diff| {err!r} = {err / scale!r} of max|ref| {scale!r}; "
        f"argmax {int(got.argmax())} vs {int(ref.argmax())}")
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=ATOL * scale,
                               err_msg=what)


def compare_steps(engine, ref_model, ref_params, prompt, *, label: str):
    """One prefill + one decode step through ``engine``'s model on its
    serve params, against ``ref_model`` on ``ref_params``. Returns the
    compiled decode step of ``engine``'s model."""
    kw = dict(slots=engine.max_slots, max_len=engine.max_len,
              page_size=engine.kv_meta.page_size)
    ref_pre, ref_dec, _, ref_c = step_logits(ref_model, ref_params,
                                             prompt, **kw)
    pre, dec, decode, c = step_logits(engine.model, engine.serve_params,
                                      prompt, **kw)
    log(f"{label}: compile s (prefill+decode) {c!r}, reference "
        f"{ref_c!r}")
    assert_close(f"{label} prefill logits", pre, ref_pre)
    assert_close(f"{label} decode logits", dec, ref_dec)
    return decode


def check_kernels_in_step(decode, bits_present) -> None:
    text = decode.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for bits in sorted(bits_present):
        name = KERNEL_OF_RUNG[bits]
        n = sum(name in ln for ln in calls)
        log(f"decode step: {n} tpu_custom_call line(s) for {name}")
        if not n:
            raise AssertionError(f"the decode step runs no {name} kernel "
                                 f"for its {bits}-bit rung")


def serve(engine, reqs, cfg):
    """Serve ``reqs`` greedily to completion at the applied plan (no
    frontier walk in between, so the checked plan is the served one);
    returns (tokens per request, seconds)."""
    rids = [engine.submit_request(ServeRequest(
        prompt=p, max_new_tokens=NEW_TOKENS)) for p in reqs]
    t0 = time.perf_counter()
    while engine.has_work():
        engine.run_iteration(temperature=0.0)   # ends in block_until_ready
    wall = time.perf_counter() - t0
    out = [engine.result(r).tokens for r in rids]
    for toks in out:
        if len(toks) != NEW_TOKENS or not all(
                0 <= t < cfg.vocab_size for t in toks):
            raise AssertionError(f"bad generation {toks}")
    return out, wall


def memory_line(device, modeled=None) -> str:
    stats = device.memory_stats() or {}
    line = (f"{device}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use')!r}, bytes_limit "
            f"{stats.get('bytes_limit')!r}")
    if modeled is not None:
        line += f"; plan's modeled device bytes {int(modeled)!r}"
    return line


def one_chip(cfg, params, seed: int, *, use_kernel=None) -> None:
    t0 = time.perf_counter()
    engine = build_engine(cfg, params,
                          engine_config(cfg, use_kernel=use_kernel))
    if not engine.use_kernel:
        raise AssertionError("the engine does not serve through the "
                             "Pallas kernels")
    target = mixed_host_target(engine.frontier)
    point = QoSController(engine).set_target(target)
    plan = engine.current_plan
    bits_present = {int(b) for b in np.unique(plan.bits)}
    n_host = int((plan.location == HOST).sum())
    log(f"target[{target.describe()}] -> {point.summary()}; host-tier "
        f"experts {n_host}; set-up s {time.perf_counter() - t0!r}; "
        f"{memory_line(jax.devices()[0])}")
    if bits_present != set(LADDER) or not n_host:
        raise AssertionError(f"plan has rungs {sorted(bits_present)} and "
                             f"{n_host} host experts")
    reqs = prompts(cfg, seed)
    oracle = build_model(engine.cfg, use_kernel=False)
    decode = compare_steps(engine, oracle, engine.serve_params, reqs[0],
                           label="kernel vs jnp oracle")
    check_kernels_in_step(decode, bits_present)
    del decode
    out, wall = serve(engine, reqs, cfg)
    m = engine.metrics
    n_tok = sum(len(t) for t in out)
    log(f"served {len(out)} requests, {n_tok} tokens in {wall!r} s "
        f"(smoke timing, first iterations compile): prefill_s "
        f"{m['prefill_s']!r}, decode_s {m['decode_s']!r}, "
        f"iterations {m['iterations']!r}")
    log(f"expert cache: {m['expert_fetches']!r} fetches of "
        f"{m['expert_accesses']!r} accesses, transfer_s "
        f"{m['transfer_s']!r}")
    if not m["expert_fetches"]:
        raise AssertionError("no host-tier expert was staged")
    log(memory_line(jax.devices()[0], point.qos.device_bytes))
    log(f"greedy tokens of request 0: {out[0]}")
    engine.close()


def shared_point(frontier, counts):
    """The fastest frontier point with these per-rung counts and no
    expert on the host tier."""
    pts = [p for p in frontier.points
           if tuple(p.counts_per_rung) == counts
           and not (p.plan.location == HOST).any()]
    if not pts:
        raise RuntimeError(f"no all-resident frontier point with counts "
                           f"{counts}")
    return max(pts, key=lambda p: p.qos.tokens_per_s)


def moe_layer(engine, x, *, use_kernel: bool) -> np.ndarray:
    """Layer 0's MoE alone (route, dispatch, expert FFN, combine, and the
    EP psum) on ``engine``'s serve banks and mesh, as its model calls it."""
    mesh = engine.mesh
    if mesh is None:                   # build_model's single-device mesh
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                                 ("data", "model"))
    par = mixed_moe.MoEParallelism(mesh=mesh, dp_axes=("data",),
                                   fsdp_axis="data")
    moe = engine.serve_params["layers"]["moe"]
    layer0 = jax.tree_util.tree_map(
        lambda a: a[0], {"router": moe["router"], "banks": moe["banks"]})

    @jax.jit
    def run(p, x):
        w, ids, _ = mixed_moe.route(p["router"], x, engine.cfg.moe,
                                    train=False)
        return mixed_moe.moe_apply(p["banks"], x, w, ids, engine.cfg.moe,
                                   par, act=engine.cfg.act,
                                   use_kernel=use_kernel)
    return np.asarray(run(layer0, x), np.float32)


def four_chips(cfg, params, seed: int, *, use_kernel=None) -> None:
    """ep=1 then ep=4, one engine at a time (device 0 holds the params and
    would not also hold two engines' banks). For each: one prefill and one
    decode step through the kernels and through the jnp oracle on the
    same serve params, layer 0's MoE alone both ways, and the greedy
    tokens the engine serves. ep=4 against ep=1 on each path says
    whether the kernels or the rest of the EP program make them differ."""
    reqs = prompts(cfg, seed)
    x = jax.random.normal(jax.random.key(seed + 1),
                          (PROMPT_LEN, cfg.d_model), jnp.bfloat16)
    out, served = {}, {}
    for ep in (1, 4):
        t0 = time.perf_counter()
        engine = build_ep_engine(cfg, params, engine_config(
            cfg, use_kernel=use_kernel), ep=ep)
        if not engine.use_kernel:
            raise AssertionError("the engine does not serve through the "
                                 "Pallas kernels")
        point = shared_point(engine.frontier, FOUR_CHIP_COUNTS)
        engine.apply_frontier_point(point)
        log(f"ep={ep}: {point.summary()}; placement "
            f"{engine.current_plan.placement_counts()}; set-up s "
            f"{time.perf_counter() - t0!r}")
        kw = dict(slots=engine.max_slots, max_len=engine.max_len,
                  page_size=engine.kv_meta.page_size)
        oracle = build_model(engine.cfg, engine.mesh, use_kernel=False)
        for path, model in (("kernel", engine.model), ("oracle", oracle)):
            pre, dec, decode, c = step_logits(model, engine.serve_params,
                                              reqs[0], **kw)
            out[path, "prefill", ep], out[path, "decode", ep] = pre, dec
            log(f"ep={ep} {path}: compile s (prefill+decode) {c!r}")
            if ep == 4 and path == "kernel":
                check_kernels_in_step(decode, {int(b) for b in np.unique(
                    engine.current_plan.bits)})
                log(f"ep=4 decode step, per device: "
                    f"{decode.memory_analysis()}")
            del decode
            out[path, "moe layer 0", ep] = moe_layer(
                engine, x, use_kernel=path == "kernel")
        served[ep], wall = serve(engine, reqs, cfg)
        log(f"ep={ep}: served {len(reqs)} requests in {wall!r} s (smoke "
            f"timing, first iterations compile)")
        engine.close()
        del engine, oracle
        gc.collect()
    for path in ("kernel", "oracle"):
        for what in ("moe layer 0", "prefill", "decode"):
            assert_close(f"ep=4 vs ep=1 {path} {what}",
                         out[path, what, 4], out[path, what, 1])
    for ep in (1, 4):
        for what in ("moe layer 0", "prefill", "decode"):
            assert_close(f"ep={ep} kernel vs oracle {what}",
                         out["kernel", what, ep], out["oracle", what, ep])
    same = sum(a == b for r1, r4 in zip(served[1], served[4])
               for a, b in zip(r1, r4))
    log(f"greedy-token agreement ep=4 vs ep=1: "
        f"{same}/{REQUESTS * NEW_TOKENS}")
    for d in jax.devices()[:4]:
        log(memory_line(d))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the ep=4 vs ep=1 phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devices = jax.devices()
    platform = devices[0].platform
    need = 4 if args.four_chips else 1
    if platform != "tpu" or len(devices) < need:
        print(f"[smoke] needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 2
    log(f"cache dir {enable_compile_cache()}")
    kind = devices[0].device_kind
    log(f"platform {platform}, device_kind {kind!r}, device count "
        f"{len(devices)}")
    cfg = smoke_config()
    log(f"config {cfg.arch_id} at published widths (d_model "
        f"{cfg.d_model}, heads {cfg.attention.num_heads}/"
        f"{cfg.attention.num_kv_heads}x{cfg.attention.head_dim}, "
        f"d_ff_expert {cfg.moe.d_ff_expert}, experts "
        f"{cfg.moe.num_experts} top-{cfg.moe.top_k}, vocab "
        f"{cfg.vocab_size}, group {cfg.mop.group_size}); reduced: "
        f"num_layers {get_config(ARCH).num_layers} -> {cfg.num_layers}")
    t0 = time.perf_counter()
    # jitted, so the f32 draws fuse into the bf16 weights: eager init
    # holds several f32 copies of an expert stack at once
    params = jax.jit(build_model(cfg).init)(jax.random.key(args.seed))
    jax.block_until_ready(params)
    log(f"random params (seed {args.seed}) in "
        f"{time.perf_counter() - t0!r} s; {memory_line(devices[0])}")
    if args.four_chips:
        four_chips(cfg, params, args.seed)
    else:
        one_chip(cfg, params, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
