"""The DeepSeek-V2 architecture file, its reference and the KV-cache
reader, at a tiny DeepSeek-V2-shaped size on the CPU: the layout is the
program's, a slice is the tree's slice, the model operations are the
products the program's layers compute, the reader reckons the bytes the
engine counts, the reference is transformers' model, and the served
paged path agrees with the reference on logits."""
from __future__ import annotations

import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import serve, spec

A = spec.load_module(spec.BENCH / "archs" / "deepseek_v2.py")
REF = spec.load_module(spec.BENCH / "references" / "deepseek_v2.py")
KV = spec.load_module(spec.BENCH / "metrics" / "decode_kv_gb_per_s.py")
CONFIG = spec.BENCH / "configs" / "deepseek-v2-lite-mixed.json"
SEED = 2**40 + 7


def _pinned_bits(c: dict) -> list:
    """The rungs the engine applies at the configuration's pinned point
    (``serve.pinned_point`` on the program's frontier)."""
    from repro.core.planner import AdaptivePlanner
    cfg = A.program_config(c)
    planner = AdaptivePlanner(cfg)
    base = planner.frontier(batch_size=4).points[0]
    point = serve.pinned_point(
        type("E", (), {"frontier": type("F", (), {"points": [base]})})(),
        c, A.expert_pairs(c))
    plan = planner.replan(float(point.qos.device_bytes), "quality",
                          point.num_q_experts, batch_size=4,
                          counts=point.quantized_counts())[0].plan
    return plan.bits.tolist()


def tiny_config() -> dict:
    """The configuration file at test widths: 1 dense and 2 expert
    layers, 8 experts top-2 with 2 shared, latent 32 + rotary 8."""
    c = json.loads(CONFIG.read_text())
    c.update({"hidden_size": 64, "intermediate_size": 128,
              "moe_intermediate_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 4, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
              "n_routed_experts": 8, "num_experts_per_tok": 2,
              "num_hidden_layers": 3, "vocab_size": 2048, "page_size": 16,
              "moe_capacity_factor": 4.0,
              "check": {"p99_logit_gap_clear": 0.02}})
    c["plan"] = dict(c["plan"], group_size=16, counts_per_rung=[4, 4, 8])
    c["plan"]["bits"] = _pinned_bits(c)
    return c


@pytest.fixture(scope="module")
def tiny():
    c = tiny_config()
    return c, A.make_params(c, SEED)


def test_published_layout_is_the_program_s():
    c = json.loads(CONFIG.read_text())
    cfg = serve.program_config(A, c)          # raises on any other layout
    a, m = cfg.attention, cfg.moe
    assert (cfg.num_layers, cfg.first_dense_layers, cfg.num_moe_layers,
            cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.padded_vocab) == \
        (5, 1, 4, 2048, 10944, 102400, 102400)
    assert (a.num_heads, a.head_dim, a.qk_rope_head_dim, a.v_head_dim,
            a.kv_lora_rank, a.kv_values_per_position) == \
        (16, 128, 64, 128, 512, 576)
    assert (m.num_experts, m.top_k, m.d_ff_expert, m.d_ff_shared,
            m.norm_topk_prob, m.routed_scaling_factor) == \
        (64, 6, 1408, 2816, False, 1.0)
    assert cfg.embedding_scale == 1.0 and A.expert_pairs(c) == 256
    assert np.asarray(c["plan"]["bits"]).shape == (4, 64)
    counts = [int((np.asarray(c["plan"]["bits"]) == b).sum())
              for b in c["plan"]["ladder"]]
    assert counts == c["plan"]["counts_per_rung"]
    tree = sum(math.prod(s) for s in A.full_shapes(c).values()) * 2
    assert tree == pytest.approx(5.68e9, rel=0.01)


def test_a_slice_is_the_tree_s_slice(tiny):
    c, p = tiny
    axes = {n: a for n, _, a in A.layout(c)}
    for name, ix in (("layers/moe/w_down", (1, 5)),
                     ("layers/attn/wkv_b", (1, 0)),
                     ("layers/shared_mlp/w_up", (0, 0)),
                     ("dense_layers/mlp/w_gate", (0, 0)),
                     ("dense_layers/attn/kv_norm/scale", (0, 0)),
                     ("lm_head/table", (0, 0))):
        node = p
        for k in name.split("/"):
            node = node[k]
        want = {"LE": lambda: node[ix[0], ix[1]], "L": lambda: node[ix[0]],
                "D": lambda: node[ix[0]], "": lambda: node}[axes[name]]()
        got = A.leaf_slice(c, SEED, name, *ix)
        assert np.array_equal(np.asarray(want), np.asarray(got))


def _dot_flops(fn, *args) -> int:
    """2 x (output elements) x (contracted size), over every matrix
    product the traced function computes."""
    def walk(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                (lc, _), _ = eqn.params["dimension_numbers"]
                a = eqn.invars[0].aval
                total += 2 * math.prod(eqn.outvars[0].aval.shape) \
                    * math.prod(a.shape[i] for i in lc)
            for v in eqn.params.values():
                if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                    total += walk(v.jaxpr)
        return total
    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def test_model_operations_are_the_program_s_products():
    """``decode_flops`` is what the program's absorbed decode step
    computes when every ring position is attended, and ``prefill_flops``
    what its expanded attention computes less the masked half of the
    scores; MLPs, the router, ``num_experts_per_tok`` experts and the
    logits of one token as written."""
    from repro.models import layers as L
    c = tiny_config()
    cfg = A.program_config(c)
    acfg, d = cfg.attention, c["hidden_size"]
    p = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape[1:], jnp.float32),
        jax.eval_shape(lambda: A.make_params(c, 0)))
    attn, dense = p["layers"]["attn"], p["dense_layers"]["mlp"]
    shared = p["layers"]["shared_mlp"]
    expert = {k: p["layers"]["moe"][k][0] for k in ("w_gate", "w_up",
                                                    "w_down")}
    mlp = lambda w: _dot_flops(lambda x: L.mlp(w, x, "swiglu"),
                               jnp.zeros((1, 1, d)))
    moe = 2 * d * c["n_routed_experts"] + c["num_experts_per_tok"] \
        * mlp(expert) + mlp(shared)
    ffn = c["first_k_dense_replace"] * mlp(dense) + A.moe_layers(c) * moe
    head = 2 * d * c["vocab_size"]
    w = 20
    ring = {"kv": jnp.zeros((1, w, acfg.kv_values_per_position)),
            "pos": jnp.arange(w)[None]}
    step = _dot_flops(lambda x: L.latent_attention(
        attn, x, acfg, positions=jnp.full((1, 1), w), cache=ring),
        jnp.zeros((1, 1, d)))
    assert A.decode_flops(c, w + 1) == 3 * step + ffn + head
    s = 24
    full = _dot_flops(lambda x: L.latent_attention(
        attn, x, acfg, positions=jnp.arange(s)[None]), jnp.zeros((1, s, d)))
    h, qk, vd = 4, 16 + 8, 16
    masked = 2 * h * (qk + vd) * (s * s - s * (s + 1) / 2)
    assert A.prefill_flops(c, s) == pytest.approx(
        3 * (full - masked) + s * ffn + head, rel=1e-12)


def _tiny_cell(tmp_path, c: dict, mix: dict):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(json.dumps(c))
    (tmp_path / "bench" / "traffic" / "mix.json").write_text(json.dumps(mix))
    bench = spec.load_benchmark()
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "bench/configs/tiny.json", "why": "test"}]
    bench["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                           "traffic": "mix", "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve("tiny.mix", root=tmp_path,
                        bench=spec.load_benchmark(tmp_path),
                        bench_dir=tmp_path / "bench")
    cell.bench_dir = spec.BENCH
    return cell


MIX = {"loop": "open", "rate_per_s": 12.0,
       "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.8,
                  "min": 8, "max": 48},
       "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                  "min": 4, "max": 16},
       "slots": 4, "max_len": 64, "check_tokens": 40}


def test_kv_reader_reckons_the_engine_s_kv_read_bytes(tmp_path, tiny):
    """Over a short window the reader's bytes equal the increments of
    the engine's ``kv_read_bytes`` in the iterations that ended inside
    it, and its GB/s is those bytes over the window's decode seconds."""
    c, _ = tiny
    setup = serve.Setup(_tiny_cell(tmp_path, c, MIX), SEED)
    eng = setup.engine
    steps = []
    run = eng.run_iteration

    def counted(**kw):
        before = eng.metrics["kv_read_bytes"]
        out = run(**kw)
        steps.append((time.perf_counter(),
                      eng.metrics["kv_read_bytes"] - before))
        return out
    eng.run_iteration = counted
    win = serve.run_window(setup, 1.0)
    want = sum(b for t, b in steps if win.t0 <= t <= win.end)
    assert want > 0
    assert KV.read_bytes(win, c) == want
    assert KV.position_bytes(c) == (32 + 8) * 2 * 3
    value = KV.read({"win": win, "config": c})
    assert value == pytest.approx(want / win.counters["decode_s"] / 1e9)
    setup.close()


def test_kv_reader_reads_gqa_bytes_and_nothing_without_decode():
    c = {"num_key_value_heads": 8, "head_dim": 128, "num_hidden_layers": 2}
    assert KV.position_bytes(c) == 2 * 8 * 128 * 2 * 2
    win = type("W", (), {"counters": {"decode_s": 0.0}, "records": []})()
    assert KV.read({"win": win, "config": c}) is None


def _hf_model(c: dict, params, dtype):
    """transformers' DeepseekV2ForCausalLM at the configuration's sizes
    with the tree's weights ((in, out) leaves transposed into Linear)."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    hf = tf.DeepseekV2Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        moe_intermediate_size=c["moe_intermediate_size"],
        num_hidden_layers=c["num_hidden_layers"],
        num_attention_heads=c["num_attention_heads"],
        num_key_value_heads=c["num_key_value_heads"],
        n_shared_experts=c["n_shared_experts"],
        n_routed_experts=c["n_routed_experts"],
        routed_scaling_factor=c["routed_scaling_factor"],
        kv_lora_rank=c["kv_lora_rank"], q_lora_rank=None,
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], qk_nope_head_dim=c["qk_nope_head_dim"],
        topk_method="greedy", n_group=1, topk_group=1,
        num_experts_per_tok=c["num_experts_per_tok"],
        first_k_dense_replace=c["first_k_dense_replace"],
        norm_topk_prob=c["norm_topk_prob"], rms_norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"], rope_scaling=None,
        tie_word_embeddings=False, attn_implementation="eager")
    model = tf.DeepseekV2ForCausalLM(hf).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    sd = {"model.embed_tokens.weight": t(params["embed"]["table"]),
          "model.norm.weight": t(params["final_norm"]["scale"]),
          "lm_head.weight": t(params["lm_head"]["table"])}
    stacks = [("dense_layers", i) for i in range(c["first_k_dense_replace"])]
    stacks += [("layers", i) for i in range(A.moe_layers(c))]
    for li, (stack, i) in enumerate(stacks):
        lp = jax.tree_util.tree_map(lambda a: a[i], params[stack])
        pre = f"model.layers.{li}."
        at = lp["attn"]
        sd.update({
            pre + "input_layernorm.weight": t(lp["attn_norm"]["scale"]),
            pre + "post_attention_layernorm.weight":
                t(lp["ffn_norm"]["scale"]),
            pre + "self_attn.q_proj.weight": t(at["wq"]).T,
            pre + "self_attn.kv_a_proj_with_mqa.weight": t(at["wkv_a"]).T,
            pre + "self_attn.kv_a_layernorm.weight":
                t(at["kv_norm"]["scale"]),
            pre + "self_attn.kv_b_proj.weight": t(at["wkv_b"]).T,
            pre + "self_attn.o_proj.weight": t(at["wo"]).T})
        proj = {"w_gate": "gate_proj", "w_up": "up_proj",
                "w_down": "down_proj"}
        if stack == "dense_layers":
            for k, n in proj.items():
                sd[pre + f"mlp.{n}.weight"] = t(lp["mlp"][k]).T
            continue
        sd[pre + "mlp.gate.weight"] = t(lp["moe"]["router"]).T
        for k, n in proj.items():
            sd[pre + f"mlp.shared_experts.{n}.weight"] = \
                t(lp["shared_mlp"][k]).T
            for e in range(c["n_routed_experts"]):
                sd[pre + f"mlp.experts.{e}.{n}.weight"] = \
                    t(lp["moe"][k][e]).T
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and not [m for m in missing
                                   if "rotary" not in m]
    return model.to(dtype)


def _ref_logits(c, seed, tokens, control=False):
    with jax.default_matmul_precision("highest"):
        x, _ = REF._hidden(c, seed, tokens, [0], [len(tokens)], control)
        table = REF._f32(A.leaf_slice(c, seed, "lm_head/table"))
        if control:
            table = REF.quant_dequant(table.T, 8, c["plan"]["group_size"]).T
        final = REF._f32(A.leaf_slice(c, seed, "final_norm/scale"))
        return np.asarray(REF._logits(
            x[:len(tokens)], final, table, eps=float(c["rms_norm_eps"]),
            vocab=c["vocab_size"], control=control))


def test_reference_is_transformers_deepseek_v2(tiny):
    """With ``rope_scaling`` off (transformers' V2 port leaves YaRN's
    softmax factor out) and every expert at bf16, the float32 reference
    gives transformers' float32 logits to float32 rounding in another
    order (1e-4 of the largest logit); transformers in bfloat16 misses
    that by far, so the comparison tells the two precisions apart."""
    torch = pytest.importorskip("torch")
    c, params = tiny
    c = dict(c, rope_scaling=None)
    c["plan"] = dict(c["plan"], bits=np.full(
        (A.moe_layers(c), c["n_routed_experts"]), 16).tolist())
    toks = np.random.default_rng(1).integers(1, c["vocab_size"], 40)
    ref = _ref_logits(c, SEED, toks.astype(np.int32))
    tol = 1e-4 * np.abs(ref).max()
    with torch.no_grad():
        ids = torch.tensor(toks[None])
        ours = _hf_model(c, params, torch.float32)(ids).logits[0].numpy()
        low = _hf_model(c, params, torch.bfloat16)(ids).logits[0] \
            .float().numpy()
    assert np.abs(ours - ref).max() < tol
    assert np.abs(low - ref).max() > 10 * tol


def test_served_paged_path_matches_the_reference(tiny, monkeypatch):
    """The engine at the pinned three-rung plan: prefill into the latent
    pages and decode through them, each step's logits (captured where
    the engine samples) against the float32 reference's full forward at
    the same positions. The program computes in bfloat16 (activations
    rounded at every product, through three layers at width 64, where
    the logits are small: 1-3 % of the largest logit on this seed): 5 %
    of the largest logit; the reference one precision lower (int8
    products, experts a rung down: 3.5-10.5 %) lies past that."""
    import repro.serving.engine as E
    from repro.serving.api import EngineConfig, ServeRequest, build_engine
    c, params = tiny
    cfg = serve.program_config(A, c)
    seen = []
    orig = E.sample

    def capture(logits, **kw):
        seen.append(np.asarray(logits, np.float32))
        return orig(logits, **kw)
    monkeypatch.setattr(E, "sample", capture)
    eng = build_engine(cfg, params, EngineConfig(
        max_slots=2, max_len=64, page_size=c["page_size"],
        ladder=tuple(c["plan"]["ladder"]), use_kernel=None, paged_kv=True))
    eng.apply_frontier_point(serve.pinned_point(eng, c, A.expert_pairs(c)))
    serve.check_plan(eng, c)
    prompt = np.random.default_rng(2).integers(
        1, c["vocab_size"], 21).astype(np.int32)
    eng.submit_request(ServeRequest(prompt=prompt, max_new_tokens=8))
    while eng.has_work():
        eng.run_iteration(temperature=0.0)
    out = next(iter(eng.done.values())).out_tokens
    got = np.stack([seen[0][0]] + [s[0] for s in seen[1:]])
    got = got[:, :c["vocab_size"]]
    seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
    ref = _ref_logits(c, SEED, seq)[len(prompt) - 1:]
    ctl = _ref_logits(c, SEED, seq, control=True)[len(prompt) - 1:]
    tol = 0.05 * np.abs(ref).max()
    assert got.shape == ref.shape == (8, c["vocab_size"])
    assert np.abs(got - ref).max() < tol
    assert np.abs(ctl - ref).max() > tol
    eng.close()
