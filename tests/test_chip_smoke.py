"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
phases hold at the smoke size with the kernels in interpret mode."""
import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core.cost_model import HardwareModel
from repro.core.planner import AdaptivePlanner
from repro.core.precision_plan import HOST
from repro.models.model import build_model
from repro.serving.api import build_engine
from repro.serving.qos import QoSController

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_tpu():
    r = _run(SCRIPT.parent)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_exits_nonzero_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("host_link_bw", [24e9, 3e9])
def test_target_puts_experts_on_every_rung_and_host(cs, host_link_bw):
    """At the published widths (cost model only, nothing is built) the
    smoke target resolves to a three-rung point with host-tier experts."""
    cfg = cs.smoke_config()
    frontier = AdaptivePlanner(
        cfg, hw=HardwareModel(host_link_bw=host_link_bw)).frontier(
            batch_size=cs.SLOTS)
    point = frontier.select(cs.mixed_host_target(frontier))
    assert set(np.unique(point.plan.bits)) == set(cs.LADDER)
    assert (point.plan.location == HOST).any()


def test_kernel_steps_match_oracle_at_smoke_size(cs):
    """The one-chip check on the CPU: prefill + decode through the
    interpret-mode kernels on a three-rung plan match the jnp oracle."""
    cfg = reduce_for_smoke(get_config(cs.ARCH))
    cfg = cfg.replace(mop=dataclasses.replace(cfg.mop, ladder=cs.LADDER))
    params = build_model(cfg).init(jax.random.key(0))
    engine = build_engine(cfg, params, cs.engine_config(cfg,
                                                        use_kernel=True))
    QoSController(engine).set_target(cs.mixed_host_target(engine.frontier))
    assert set(np.unique(engine.current_plan.bits)) == set(cs.LADDER)
    oracle = build_model(engine.cfg, use_kernel=False)
    cs.compare_steps(engine, oracle, engine.serve_params,
                     cs.prompts(cfg, 0)[0], label="cpu")
    engine.close()
