"""EP parity suite (DESIGN.md §4 / §16).

Token-gather EP dispatch (§Perf kimi iteration B1): numerical
equivalence with the dense oracle and with the weight-gather path, plus
the regime gate. Extended for the EP serving mesh: decode over a
(1, ep) mesh must be BIT-identical to the single-device loop for
EP ∈ {1, 2, 4} — on binary and mixed (16, 8, 4) plans, and across a
replan that migrates experts between EP ranks (bank membership change).
Every multi-device case runs in a subprocess that forces the host
device count BEFORE importing jax."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.core import mixed_moe as MM

pytestmark = pytest.mark.skipif(
    jax.device_count() != 1, reason="spawns its own multi-device subprocess")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp, numpy as np
from repro.core import mixed_moe as MM
from repro.configs.base import MoEConfig

mesh = jax.make_mesh((4, 4), ("data", "model"))
moe = MoEConfig(num_experts=8, top_k=2, d_ff_expert=64, capacity_factor=8.0)
d, t = 32, 16
ks = jax.random.split(jax.random.key(0), 5)
params = {
    "router": jax.random.normal(ks[0], (d, 8), jnp.float32) * 0.1,
    "w_gate": jax.random.normal(ks[1], (8, d, 64), jnp.bfloat16) * 0.1,
    "w_up": jax.random.normal(ks[2], (8, d, 64), jnp.bfloat16) * 0.1,
    "w_down": jax.random.normal(ks[3], (8, 64, d), jnp.bfloat16) * 0.1,
}
x = jax.random.normal(ks[4], (t, d), jnp.bfloat16)
ref = MM.moe_dense_ref(params, x, moe)
banks16 = {"q4": None,
           "f16": {k: params[k] for k in ("w_gate", "w_up", "w_down")}}
w, ids, _ = MM.route(params["router"], x, moe, train=False)
outs = {}
with jax.set_mesh(mesh):
    for fsdp in (None, "data"):
        par = MM.MoEParallelism(mesh=mesh, dp_axes=("data",),
                                fsdp_axis=fsdp)
        y = MM.moe_apply(banks16, x, w, ids, moe, par)
        err = float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        outs[str(fsdp)] = err
for k, v in outs.items():
    print(f"RESULT {k} {v:.6f}")
assert all(v < 5e-3 for v in outs.values()), outs
print("OK")
"""


_PARITY_SCRIPT = r"""
import contextlib
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduce_for_smoke
from repro.core.precision_plan import balanced_ladder_plan
from repro.launch.mesh import make_ep_mesh
from repro.models.model import apply_precision_plan, build_model

cfg = reduce_for_smoke(get_config("mixtral-8x7b"))
L, E, gs = cfg.num_layers, cfg.moe.num_experts, cfg.mop.group_size
base = build_model(cfg)
params = base.init(jax.random.key(0))
# per-layer bank sizes must divide by the largest ep under test (4)
plans = {
    "binary": balanced_ladder_plan(L, E, {4: 4 * L},
                                   ladder=(16, 4), group_size=gs),
    "mixed": balanced_ladder_plan(L, E, {4: 4 * L, 8: 4 * L},
                                  ladder=(16, 8, 4), group_size=gs),
    "replan": balanced_ladder_plan(L, E, {4: 8 * L},
                                   ladder=(16, 4), group_size=gs),
}
tok = np.asarray(jax.random.randint(jax.random.key(1), (2, 8), 1,
                                    cfg.vocab_size))
ref = {}
for name in PLANS:
    plan = plans[name]
    sp = apply_precision_plan(params, cfg, plan)
    for ep in (1, 2, 4):
        mesh = None if ep == 1 else make_ep_mesh(ep)
        model = build_model(cfg, mesh, use_kernel=USE_KERNEL)
        ctx = jax.set_mesh(mesh) if mesh is not None \
            else contextlib.nullcontext()
        with ctx:
            cache = model.init_cache(2, 24)
            logits, cache = model.prefill(sp, {"tokens": jnp.asarray(tok)},
                                          cache)
            chunks = [np.asarray(jax.device_get(logits)).tobytes()]
            cur = jnp.argmax(logits, -1)[:, None]
            pos = jnp.full((2,), tok.shape[1], jnp.int32)
            for step in range(4):
                logits, cache = model.decode_step(sp, cache, cur,
                                                  pos + step)
                chunks.append(np.asarray(jax.device_get(logits)).tobytes())
                cur = jnp.argmax(logits, -1)[:, None]
        blob = b"".join(chunks)
        if ep == 1:
            ref[name] = blob
        assert blob == ref[name], f"{name}: ep={ep} diverges from ep=1"
    print(f"PARITY {name} OK")
# the replan moved every f16 expert into the q4 bank: bank membership
# changed, so the contiguous per-bank sharding migrates experts between
# EP ranks -- and decode stayed bit-identical on both sides (above)
a = plans["binary"].device_assignment(4)
b = plans["replan"].device_assignment(4)
assert (a != b).any(), "replan migrated no expert between EP ranks"
print("MIGRATION OK")
print("OK")
"""

_ENGINE_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings
import jax, numpy as np
from repro.configs import get_config, reduce_for_smoke
from repro.models.model import build_model
from repro.serving.api import EngineConfig
from repro.serving.ep import build_ep_engine

cfg = reduce_for_smoke(get_config("mixtral-8x7b"))
model = build_model(cfg)
params = model.init(jax.random.key(0))
outs = {}
for ep in (1, 2):
    eng = build_ep_engine(cfg, params,
                          EngineConfig(max_slots=2, max_len=16), ep=ep)
    full = eng.planner.size_ne + \
        eng.planner.num_experts_total * eng.planner.size_e16
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        eng.configure(full, "quality", 4 * cfg.num_layers)
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(1, cfg.vocab_size, 6),
                       max_new_tokens=4) for _ in range(3)]
    eng.step(temperature=0.0)
    # mid-deployment replan: every expert drops to q4, bank membership
    # changes, experts migrate between EP ranks
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        eng.configure(full, "quality", 8 * cfg.num_layers)
    rids2 = [eng.submit(rng.integers(1, cfg.vocab_size, 6),
                        max_new_tokens=4) for _ in range(3)]
    eng.step(temperature=0.0)
    outs[ep] = ([eng.result(r).tokens for r in rids],
                [eng.result(r).tokens for r in rids2])
    eng.close()
assert outs[1] == outs[2], outs
print("OK")
"""


def _parity_script(plans, use_kernel):
    return (f"PLANS = {list(plans)!r}\nUSE_KERNEL = {use_kernel!r}\n"
            + _PARITY_SCRIPT)


def _run_sub(script, timeout=900):
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(
                   os.path.join(os.path.dirname(__file__), "..", "src")))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)


class TestEPDecodeParity:
    """Serving-mesh bit-identity (DESIGN.md §16, ISSUE acceptance)."""

    def test_decode_bit_identical_across_ep(self):
        """prefill + 4 greedy decode steps: logits BYTES equal for
        EP ∈ {1, 2, 4}, on binary and mixed (16, 8, 4) plans, plus the
        rank-migration assertion across a replan."""
        r = _run_sub(_parity_script(("binary", "mixed", "replan"), False))
        assert "OK" in r.stdout and "MIGRATION OK" in r.stdout, \
            r.stdout + r.stderr

    def test_kernel_decode_bit_identical_across_ep(self):
        """The same on the mixed (16, 8, 4) plan through the Pallas
        kernels (interpret mode here): the grouped kernels inside the
        EP shard_map give ep=2 and ep=4 the bytes of ep=1."""
        r = _run_sub(_parity_script(("mixed",), True))
        assert r.returncode == 0 and "PARITY mixed OK" in r.stdout, \
            r.stdout + r.stderr

    def test_engine_tokens_identical_across_ep(self):
        """Full engine (scheduler + paged KV + replan) on a (1, 2) mesh
        generates the same greedy tokens as the single-device engine,
        including after a rung replan that migrates experts."""
        r = _run_sub(_ENGINE_PARITY_SCRIPT)
        assert "OK" in r.stdout, r.stdout + r.stderr


class TestTokenGatherEP:
    def test_matches_oracle_on_mesh(self):
        env = dict(os.environ,
                   PYTHONPATH=os.path.abspath(
                       os.path.join(os.path.dirname(__file__), "..", "src")))
        env.pop("XLA_FLAGS", None)
        r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                           capture_output=True, text=True, timeout=300)
        assert "OK" in r.stdout, r.stdout + r.stderr

    def test_regime_gate_decode_vs_train(self):
        """Gate math: decode token sets gather; train-scale don't."""
        d = 7168
        fs = 16
        decode_tokens = 128 // 16          # per dp rank
        train_tokens = 65536 // 16
        assert decode_tokens * fs * d * 2 <= MM.TOKEN_GATHER_MAX_BYTES
        assert train_tokens * fs * d * 2 > MM.TOKEN_GATHER_MAX_BYTES

    def test_fsdp_inactive_without_axis(self):
        """fsdp never activates on a 1-device mesh / without the axis."""
        moe = MoEConfig(num_experts=8, top_k=2, d_ff_expert=64)
        dev = np.array(jax.devices()[:1]).reshape(1, 1)
        mesh = jax.sharding.Mesh(dev, ("data", "model"))
        par = MM.MoEParallelism(mesh=mesh, dp_axes=("data",),
                                fsdp_axis="data")
        assert par.fsdp_size == 1
        banks = {"q4": None,
                 "f16": {"w_gate": jnp.zeros((8, 32, 64), jnp.bfloat16),
                         "w_up": jnp.zeros((8, 32, 64), jnp.bfloat16),
                         "w_down": jnp.zeros((8, 64, 32), jnp.bfloat16)}}
        assert not MM._fsdp_active(banks, moe, par, ep=True)
