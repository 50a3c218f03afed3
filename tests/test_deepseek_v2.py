"""DeepSeek-V2's mechanisms at a tiny size on the CPU (DESIGN.md §13):
latent attention (absorbed decode over the latent cache against the
expanded form), YaRN rotary frequencies against transformers', routing
without renormalisation, shared experts off the ladder, the leading dense
layer, and the served paged path against the model's own full forward.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import mixed_moe
from repro.models import layers as L
from repro.models.model import build_model
from repro.models.transformer import _ffn_or_moe


@pytest.fixture(scope="module")
def tiny():
    cfg = reduce_for_smoke(get_config("deepseek-v2-lite"))
    return cfg, build_model(cfg).init(jax.random.key(3))


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def test_tiny_config_keeps_the_mechanisms(tiny):
    cfg, _ = tiny
    a, m = cfg.attention, cfg.moe
    assert a.latent and a.yarn is not None
    assert not m.norm_topk_prob and m.d_ff_shared
    assert cfg.first_dense_layers == 1 and cfg.num_moe_layers == 1
    assert cfg.embedding_scale == 1.0
    assert cfg.attention.kv_values_per_position == \
        a.kv_lora_rank + a.qk_rope_head_dim


def test_yarn_frequencies_match_transformers():
    """The program's YaRN inverse frequencies at DeepSeek-V2-Lite's
    published rotary (64 wide, theta 1e4, factor 40 over 4096, beta
    32/1) against transformers' ``_compute_yarn_parameters``; both
    float32 roundings of one formula (``rtol`` 1e-6). mscale equals
    mscale_all_dim, so cos and sin are not scaled (factor 1)."""
    pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    from transformers.modeling_rope_utils import _compute_yarn_parameters
    a = get_config("deepseek-v2-lite").attention
    hf = tf.DeepseekV2Config(
        qk_rope_head_dim=a.qk_rope_head_dim, rope_theta=a.rope_theta,
        max_position_embeddings=163840,
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 0.707,
                      "mscale_all_dim": 0.707,
                      "original_max_position_embeddings": 4096})
    inv, factor = _compute_yarn_parameters(hf, "cpu")
    np.testing.assert_allclose(L.rotary_inv_freq(a, a.qk_rope_head_dim),
                               inv.numpy(), rtol=1e-6)
    y = a.yarn
    assert L.yarn_mscale(y.factor, y.mscale) / L.yarn_mscale(
        y.factor, y.mscale_all_dim) == pytest.approx(factor)
    # the softmax's mscale(40, 0.707)**2 that transformers' V2 port omits
    assert L.latent_scale(a) == pytest.approx(192 ** -0.5 * 1.5896, 1e-4)


def test_absorbed_decode_matches_expanded(tiny):
    """A decode step through the latent cache (the query folded into the
    latent space, scores against the cached latents) equals the
    expanded attention of the whole sequence at its last position. All
    in float32: the same products in another order (rtol 1e-5)."""
    cfg, params = tiny
    a = cfg.attention
    p = _f32(jax.tree_util.tree_map(lambda v: v[0],
                                    params["layers"]["attn"]))
    s, d = 12, cfg.d_model
    x = jax.random.normal(jax.random.key(1), (2, s, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (2, s))
    full, _ = L.latent_attention(p, x, a, positions=pos)
    ring = {"kv": jnp.zeros((2, 16, a.kv_values_per_position)),
            "pos": jnp.full((2, 16), -1, jnp.int32)}
    _, ring = L.latent_attention(p, x[:, :-1], a, positions=pos[:, :-1],
                                 cache=ring)
    step, entry = L.latent_attention(p, x[:, -1:], a,
                                     positions=pos[:, -1:], cache=ring)
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-5,
                               atol=1e-5)
    assert entry["kv"].shape == (2, 1, a.kv_values_per_position)
    assert (np.asarray(entry["pos"]) == s - 1).all()


def test_route_without_renormalisation():
    moe = get_config("deepseek-v2-lite").moe
    x = jax.random.normal(jax.random.key(0), (5, 16))
    w = jax.random.normal(jax.random.key(1), (16, moe.num_experts))
    probs = jax.nn.softmax(x @ w, axis=-1)
    top = np.sort(np.asarray(probs), axis=-1)[:, ::-1][:, :moe.top_k]
    weights, ids, _ = mixed_moe.route(w, x, moe, train=False)
    np.testing.assert_allclose(np.sort(np.asarray(weights))[:, ::-1], top,
                               rtol=1e-6)
    assert (np.asarray(weights).sum(-1) < 1).all()
    renorm = dataclasses.replace(moe, norm_topk_prob=True,
                                 routed_scaling_factor=2.0)
    w2, ids2, _ = mixed_moe.route(w, x, renorm, train=False)
    np.testing.assert_array_equal(ids, ids2)
    np.testing.assert_allclose(np.asarray(w2).sum(-1), 2.0, rtol=1e-6)


def test_shared_experts_add_to_the_routed_output(tiny):
    cfg, params = tiny
    p = jax.tree_util.tree_map(lambda v: v[0], params["layers"])
    xn = jax.random.normal(jax.random.key(2), (2, 3, cfg.d_model),
                           jnp.bfloat16)
    par = mixed_moe.MoEParallelism(
        mesh=jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                               ("data", "model")), dp_axes=("data",))
    with_shared, _, _ = _ffn_or_moe(p, xn, cfg, par, False, False, {})
    routed, _, _ = _ffn_or_moe({k: v for k, v in p.items()
                                if k != "shared_mlp"}, xn, cfg, par,
                               False, False, {})
    want = routed + L.mlp(p["shared_mlp"], xn, cfg.act)
    np.testing.assert_array_equal(np.asarray(with_shared, np.float32),
                                  np.asarray(want, np.float32))


def test_leading_dense_layer_runs_its_mlp(tiny):
    """The forward is the dense layer (attention, dense MLP) and then the
    expert layer, each around its residual: spelled out by hand."""
    cfg, params = tiny
    model = build_model(cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, (1, 10)), jnp.int32)
    cache = model.init_cache(1, 16)
    logits, _ = jax.jit(model.prefill)(params, {"tokens": toks}, cache)

    pos = jnp.arange(10)[None]
    x = jnp.take(params["embed"]["table"], toks, axis=0)
    for stack in ("dense_layers", "layers"):
        p = jax.tree_util.tree_map(lambda v: v[0], params[stack])
        h, _ = L.latent_attention(
            p["attn"], L.rms_norm(x, p["attn_norm"]["scale"]),
            cfg.attention, positions=pos)
        x = x + h
        xn = L.rms_norm(x, p["ffn_norm"]["scale"])
        if stack == "dense_layers":
            x = x + L.mlp(p["mlp"], xn, cfg.act)
        else:
            par = mixed_moe.MoEParallelism(
                mesh=jax.sharding.Mesh(
                    np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model")), dp_axes=("data",))
            x = x + _ffn_or_moe(p, xn, cfg, par, False, False, {})[0]
    y = L.rms_norm(x[:, -1:], params["final_norm"]["scale"])
    want = L.unembed(params["lm_head"]["table"], y)[:, 0]
    # the same bf16 operations, traced once inside a scan and once here
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=2e-2, atol=2e-2 * float(
                                   jnp.abs(want).max()))


def test_paged_decode_matches_the_full_forward(tiny):
    """Prefill into the latent paged pool, then decode step by step
    (each layer reading its page view in place, new entries written
    back): every step's logits equal the full forward's at that
    position, within bf16 rounding of the same bf16 model (2 % of the
    largest logit; the expanded and absorbed forms round differently)."""
    cfg, params = tiny
    model = build_model(cfg)
    rng = np.random.default_rng(5)
    n, extra = 7, 5
    toks = rng.integers(1, cfg.vocab_size, n + extra).astype(np.int32)
    pool, meta = model.init_paged_cache(2, 32, page_size=8)
    table = np.zeros((2, meta.chunks_per_slot), np.int32)
    table[1] = np.arange(1, meta.chunks_per_slot + 1)
    sb = 8
    t = np.zeros((1, sb), np.int32)
    p = np.full((1, sb), -1, np.int32)
    t[0, :n], p[0, :n] = toks[:n], np.arange(n)
    pre = jax.jit(lambda *a: model.paged_prefill_into_slot(
        *a, window=meta.window))
    dec = jax.jit(lambda *a: model.paged_decode_step_routed(
        *a, window=meta.window))
    got, pool = pre(params, pool, jnp.asarray(table[1]), jnp.asarray(t),
                    jnp.asarray(p), jnp.int32(n - 1))
    steps = [np.asarray(got[0])]
    for i in range(n, n + extra - 1):
        tok = np.zeros((2, 1), np.int32)
        pos = np.array([-1, i], np.int32)
        tok[1, 0] = toks[i]
        lg, pool, _ = dec(params, pool, jnp.asarray(table),
                          jnp.asarray(tok), jnp.asarray(pos))
        steps.append(np.asarray(lg[1]))
    assert (np.asarray(pool["pos"][:, 0]) == -1).all()   # null page clean
    full = []
    for i in range(n, n + extra):
        cache = model.init_cache(1, 32)
        lg, _ = jax.jit(model.prefill)(
            params, {"tokens": jnp.asarray(toks[None, :i])}, cache)
        full.append(np.asarray(lg[0]))
    full = np.stack(full)
    np.testing.assert_allclose(np.stack(steps), full, rtol=0,
                               atol=2e-2 * np.abs(full).max())
