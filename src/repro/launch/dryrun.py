import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

MUST be imported before anything that initializes jax — the two lines above
create 512 host placeholder devices so the production meshes (16,16) and
(2,16,16) can be built. Do NOT set this flag globally (smoke tests and
benches run on 1 device).

Per cell this driver:
  1. builds the step function (train_step / prefill / decode_step),
  2. lowers + compiles it AOT against ShapeDtypeStruct inputs with the
     production shardings (no allocation),
  3. records memory_analysis / cost_analysis / collective bytes parsed from
     the compiled HLO (roofline inputs) into results/dryrun/*.json.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape decode_32k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, all_cells, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.models.model import (abstract_params, apply_precision_plan,
                                build_model, init_cache)
from repro.training.train_loop import (TrainConfig, make_train_step,
                                       opt_state_specs, init_train_state)
from repro.training.optimizer import OptConfig

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# Serving cells for MoE archs lower the paper's mixed-precision banks:
# half the experts 4-bit (per-layer balanced; EP needs multiples of 16).
MOP_FRACTION = 0.5


def _serve_params_struct(cfg: ModelConfig):
    """Abstract serve-layout params (mixed banks) via eval_shape."""
    if cfg.moe is None or not cfg.mop.enabled:
        return abstract_params(cfg)
    from repro.core.precision_plan import balanced_random_plan
    e = cfg.moe.num_experts
    per_layer = int(e * MOP_FRACTION)
    per_layer -= per_layer % 16 if e >= 16 else 0
    plan = balanced_random_plan(cfg.num_layers, e,
                                per_layer * cfg.num_layers,
                                bits=cfg.mop.bits,
                                group_size=cfg.mop.group_size)
    fn = functools.partial(apply_precision_plan, cfg=cfg, plan=plan)
    return jax.eval_shape(fn, abstract_params(cfg))


def pick_train_cfg(cfg: ModelConfig, shape: ShapeConfig, mesh) -> TrainConfig:
    dp = SH.batch_axes(mesh, shape.global_batch)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    b_loc = max(shape.global_batch // n_dp, 1)
    # one sequence per device per microstep bounds activation memory
    n_micro = b_loc
    opt = "adafactor" if cfg.param_count() > 2e11 else "adamw"
    return TrainConfig(opt=OptConfig(), optimizer=opt,
                       num_microbatches=n_micro)


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Returns (fn, example_args (SDS), in_shardings, out_shardings|None,
    donate)."""
    dp = SH.batch_axes(mesh, shape.global_batch)
    model = build_model(cfg, mesh, dp_axes=dp)
    ns = lambda tree: SH.param_shardings(cfg, mesh, tree)

    if shape.kind == "train":
        cfg_t = cfg.replace(remat="full")
        model_t = build_model(cfg_t, mesh, dp_axes=dp)
        tcfg = pick_train_cfg(cfg, shape, mesh)
        step = make_train_step(model_t.loss_fn, tcfg)
        params = abstract_params(cfg)
        opt_state = jax.eval_shape(
            functools.partial(init_train_state, tcfg=tcfg), params)
        batch, batch_sh = SH.input_specs(cfg, shape, mesh)
        p_spec = SH.param_specs(cfg, mesh, params)
        o_spec = opt_state_specs(p_spec, tcfg, params)
        to_ns = lambda t: jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        in_sh = (to_ns(p_spec), to_ns(o_spec), batch_sh)
        out_sh = (to_ns(p_spec), to_ns(o_spec), None)
        return step, (params, opt_state, batch), in_sh, out_sh, (0, 1)

    serve_params = _serve_params_struct(cfg)
    p_sh = ns(serve_params)
    cache, cache_sh = SH.cache_specs(cfg, shape, mesh)
    if shape.kind == "prefill":
        batch, batch_sh = SH.input_specs(cfg, shape, mesh)
        fn = lambda params, batch, cache: model.prefill(params, batch, cache)
        return fn, (serve_params, batch, cache), \
            (p_sh, batch_sh, cache_sh), None, (2,)
    # decode
    inp, inp_sh = SH.input_specs(cfg, shape, mesh)
    fn = lambda params, cache, tokens, positions: model.decode_step(
        params, cache, tokens, positions)
    return fn, (serve_params, cache, inp["tokens"], inp["positions"]), \
        (p_sh, cache_sh, inp_sh["tokens"], inp_sh["positions"]), None, (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True, extra_tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    tag = "pod2x16x16" if multi_pod else "pod16x16"
    out = {"arch": arch, "shape": shape_name, "mesh": tag,
           "params_b": cfg.param_count() / 1e9,
           "active_params_b": cfg.active_param_count() / 1e9}
    t0 = time.time()
    try:
        fn, args, in_sh, out_sh, donate = build_cell(cfg, shape, mesh)
        jit_kw = dict(in_shardings=in_sh)
        if out_sh is not None:
            jit_kw["out_shardings"] = out_sh
        with jax.set_mesh(mesh):
            jfn = jax.jit(fn, **jit_kw)
            lowered = jfn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        out.update({
            "ok": True,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "code_bytes": int(ma.generated_code_size_in_bytes),
                "peak_per_device_gib": round(
                    (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes) / 2**30, 3),
            },
            "cost_analysis": {k: float(v) for k, v in ca.items()
                              if isinstance(v, (int, float))
                              and k in ("flops", "bytes accessed",
                                        "transcendentals")},
        })
        # roofline inputs: collective bytes + trip-count-corrected
        # FLOPs/traffic from compiled HLO (cost_analysis counts a scanned
        # layer body once — see roofline/hlo_parse.py)
        from repro.roofline.hlo_parse import collective_summary, cost_summary
        hlo = compiled.as_text()
        out["collectives"] = collective_summary(hlo)
        out["hlo_cost"] = cost_summary(hlo)
        # TPU-target view: CPU-backend f32-promotion artifacts removed
        # (the roofline's memory term uses this; raw kept for reference)
        out["hlo_cost_tpu"] = cost_summary(hlo, tpu_adjusted=True)
        out["hlo_bytes"] = len(hlo)
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweep
        out.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    out["total_s"] = round(time.time() - t0, 2)
    if save:
        RESULTS.mkdir(parents=True, exist_ok=True)
        name = f"{arch}__{shape_name}__{tag}{extra_tag}.json"
        (RESULTS / name).write_text(json.dumps(out, indent=1))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    cells = list(all_cells()) if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_fail = 0
    for arch, shape_name in cells:
        for mp in meshes:
            tag = "pod2x16x16" if mp else "pod16x16"
            path = RESULTS / f"{arch}__{shape_name}__{tag}.json"
            if path.exists() and not args.force:
                prev = json.loads(path.read_text())
                if prev.get("ok"):
                    print(f"[skip] {arch} {shape_name} {tag} (cached ok)")
                    continue
            r = run_cell(arch, shape_name, mp)
            status = "OK " if r["ok"] else "FAIL"
            mem = r.get("memory", {}).get("peak_per_device_gib", "-")
            print(f"[{status}] {arch:22s} {shape_name:12s} {tag:10s} "
                  f"peak/dev={mem}GiB t={r['total_s']}s"
                  + ("" if r["ok"] else f"  {r['error'][:120]}"))
            n_fail += 0 if r["ok"] else 1
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
