"""One run of one cell: set-up, the window (traced or not), the metrics,
and the comparison with the plain reference that decides ``correct``."""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, Optional

import numpy as np

from benchlib import roofline

#: a router margin past which bfloat16 rounding cannot change the
#: experts chosen: about 7x the rounding of a router logit (PERF.md)
ROUTE_MARGIN = 0.02


def trace_options():
    """Device ops and the harness's annotations; no Python tracer."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def p95(values) -> Optional[float]:
    return float(np.percentile(values, 95)) if len(values) else None


def end_to_end(win) -> Dict[str, float]:
    due = [r for r in win.records if r.due < win.end]
    ttft = [(r.first() - r.due) * 1e3 for r in due if r.stamps]
    gaps = [(b - a) * 1e3 for r in win.records
            for a, b in zip(r.stamps, r.stamps[1:])
            if win.t0 <= a and b <= win.end]
    toks = sum(1 for r in win.records for s in r.stamps
               if win.t0 <= s <= win.end)
    return {"ttft_p95_ms": p95(ttft), "itl_p95_ms": p95(gaps),
            "output_tokens_per_s": toks / (win.end - win.t0)}


def gap_stats(gaps, margins) -> Dict[str, float]:
    """The widest, 99th-percentile and mean gap over every compared
    token, and the same over the tokens whose routing is clear: where no
    layer's router has its last chosen and first unchosen expert within
    ``ROUTE_MARGIN`` of each other, so rounding cannot choose another
    expert."""
    g = np.asarray(gaps, np.float64)
    clear = g[np.asarray(margins) >= ROUTE_MARGIN]
    inf = float("inf")
    return {"p99_logit_gap_clear":
            float(np.percentile(clear, 99)) if len(clear) else inf,
            "mean_logit_gap_clear": float(clear.mean()) if len(clear) else inf,
            "max_logit_gap_clear": float(clear.max()) if len(clear) else inf,
            "max_logit_gap": float(g.max()) if len(g) else inf,
            "p99_logit_gap": float(np.percentile(g, 99)) if len(g) else inf,
            "mean_logit_gap": float(g.mean()) if len(g) else inf,
            "tokens": int(len(g)), "tokens_clear": int(len(clear))}


def judge(stats: dict, c: dict, mix: dict, failed: int):
    """``correct`` and the numbers compared beside their limits: each of
    the configuration's ``check`` numbers at most its limit, at least
    ``check_tokens`` tokens compared, and no request left unserved."""
    checks = {name: {"value": stats[name], "limit": float(limit)}
              for name, limit in c["check"].items()}
    checks["tokens_compared"] = {"value": stats["tokens"],
                                 "limit": int(mix["check_tokens"])}
    correct = bool(
        all(ch["value"] <= ch["limit"] for n, ch in checks.items()
            if n != "tokens_compared")
        and checks["tokens_compared"]["value"]
        >= checks["tokens_compared"]["limit"] and failed == 0)
    return correct, checks


def sample_for_check(win, seed: int, want_tokens: int):
    """Finished requests drawn from the seed, the longest first, until
    they hold ``want_tokens`` served tokens."""
    done = [r for r in win.records if r.req.t_done is not None]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i].req.out_tokens), -i))
    rest = [done[i] for i in np.random.default_rng(
        [seed & 0xFFFFFFFF, seed >> 32, 6]).permutation(len(done))
        if i != longest]
    picked, n = [], 0
    for r in [done[longest]] + rest:
        if n >= want_tokens:
            break
        picked.append((np.asarray(r.req.prompt), list(r.req.out_tokens)))
        n += len(r.req.out_tokens)
    return picked


def run(cell, *, seed: int, seconds: float, trace: bool = False,
        control: bool = False, t_start: float,
        engine_hook: Optional[Callable] = None) -> dict:
    import jax

    from benchlib import serve, trace as TR
    dev = jax.devices()[0]
    pk = roofline.peaks(dev.device_kind) if dev.platform == "tpu" else None
    c, mix = cell.config, cell.traffic
    log(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {seed}, {seconds} s, trace {int(trace)}; "
        f"device {dev.platform} {dev.device_kind!r} x{len(jax.devices())}")
    setup = serve.Setup(cell, seed, engine_hook=engine_hook)
    log(f"set-up parts s: {setup.times}; prefill buckets "
        f"{len(setup.buckets)} {setup.buckets}")
    log(f"device bytes reckoned: {setup.device_bytes()}")
    tdir = ann = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir, profiler_options=trace_options())
        ann = jax.profiler.TraceAnnotation
    setup_s = time.perf_counter() - t_start
    win = serve.run_window(setup, seconds, trace_annotation=ann)
    trace_sum = None
    if trace:
        jax.profiler.stop_trace()
        t = time.perf_counter()
        trace_sum = TR.reduce_dir(tdir, pk, c, cell.kernels())
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t!r} s; idle s by "
            f"span {json.dumps(trace_sum['idle_by_span'])}; programs "
            f"{json.dumps(trace_sum['modules'])}")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"peak_bytes_in_use after the window {peak}; bytes_limit "
        f"{stats.get('bytes_limit')}")
    late = [r.submitted - r.due for r in win.records]
    log(f"window: {len(win.records)} requests sent, generator lateness "
        f"max {max(late, default=0.0)!r} s, p95 {p95(late)!r} s; drain "
        f"{win.drain_s!r} s; iterations {win.iterations}; traces, compiles "
        f"and cache loads in the window {win.compiles}; engine counters "
        f"{win.counters}")
    log(f"longest in the window (offset s, s): {json.dumps(win.stalls)}")
    attempted = [r for r in win.records if r.due < win.end]
    failed = sum(1 for r in attempted if not r.stamps)
    result = {"correct": None, "attempted": len(attempted),
              "failed": failed, "metrics": {}, "device": {
                  "platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}}
    if trace:
        ctx = {"win": win, "config": c, "traffic": mix, "peaks": pk,
               "trace": trace_sum, "seconds": seconds}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"]["busy_s"] = trace_sum["busy_s"]
        result["device"]["window_s"] = trace_sum["window_s"]
        result["breakdown"] = trace_sum["breakdown"]
    else:
        e2e = end_to_end(win)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    log(f"metrics {json.dumps(result['metrics'])}")
    waited = serve.serve_until_finished(setup, win, int(mix["check_tokens"]))
    if waited > 0.5:
        log(f"served {waited!r} s past the window for finished requests")
    seqs = sample_for_check(win, seed, int(mix["check_tokens"]))
    del win
    setup.close()
    del setup
    gc.unfreeze()               # set-up froze the engine's objects
    gc.collect()
    t = time.perf_counter()
    ref = cell.reference().served_gaps(c, seed, seqs, control=control) \
        if seqs else {"gaps": np.zeros(0), "route_margins": np.zeros(0)}
    stats = gap_stats(ref["gaps"], ref["route_margins"])
    log(f"reference over {len(seqs)} requests in "
        f"{time.perf_counter() - t!r} s: program {stats}")
    correct, checks = judge(stats, c, mix, failed)
    if control and seqs:
        # the control in the program's place: its gaps decide the run
        result["program"] = dict(stats, correct=correct)
        stats = gap_stats(ref["control_gaps"], ref["route_margins"])
        log(f"control {stats}")
        correct, checks = judge(stats, c, mix, failed)
    result["correct"] = correct
    result["checks"] = checks
    return result


def emit(result: dict) -> None:
    for name, chk in result["checks"].items():
        print(f"[check] {name} {chk['value']!r} limit {chk['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
