"""Set-up and the measured window. This is the one module of the
harness that imports the program under test (an architecture file's
``program_config`` builds the program's configuration), and it uses the
program only as a served path: ``build_engine``, ``apply_frontier_point``,
``submit_request``, ``run_iteration`` and ``enable_compile_cache``, plus
the scheduler's request records for their timestamps and the engine's
counters. Whatever depends on the architecture it asks the
configuration's architecture file (``bench/archs/<arch>.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from benchlib import traffic as T

from repro.core.precision_plan import HOST
from repro.serving.api import EngineConfig, ServeRequest, build_engine

#: a memory budget no plan reaches: every expert of a pinned plan resident
ALL_RESIDENT_BUDGET = float(1 << 50)


def program_config(arch, c: dict):
    """The program's ModelConfig for configuration file ``c``, from its
    architecture file; refuses a program whose parameter layout differs
    from the tree the architecture makes."""
    cfg = arch.program_config(c)
    ours = arch.full_shapes(c)
    theirs = {n: tuple(s) for n, s in cfg.param_shapes()}
    if ours != theirs:
        raise RuntimeError(f"the program's parameter layout {theirs} is "
                           f"not the benchmark's {ours}")
    return cfg


def pinned_point(engine, c: dict, experts: int):
    """A frontier point with the configuration's per-rung counts and a
    budget that holds all ``experts`` (layer, expert) pairs on the
    device. The frontier keeps no all-resident point with three rungs, so
    the point is built from one of its points with these counts in place
    of that point's."""
    plan = c["plan"]
    counts = tuple(int(x) for x in plan["counts_per_rung"])
    base = engine.frontier.points[0]
    q = sum(n for b, n in zip(plan["ladder"], counts) if b < 16)
    return dataclasses.replace(
        base, num_q_experts=q, resident_experts=experts,
        counts_per_rung=counts, peer_experts=0,
        qos=dataclasses.replace(base.qos,
                                device_bytes=ALL_RESIDENT_BUDGET))


def check_plan(engine, c: dict) -> None:
    plan = engine.current_plan
    want = np.asarray(c["plan"]["bits"])
    if not np.array_equal(np.asarray(plan.bits), want):
        raise RuntimeError(f"applied rungs {plan.bits.tolist()} are not "
                           f"the configuration's {want.tolist()}")
    if (np.asarray(plan.location) == HOST).any():
        raise RuntimeError("the applied plan keeps experts on the host")


def prefill_buckets(c: dict, mix: dict) -> List[int]:
    """The padded prompt lengths the engine compiles for this mix: whole
    pages, at most the slot window."""
    ps, window = c["page_size"], mix["max_len"]
    lo, hi = T.length_ranges(mix)[:2]
    return sorted({min(-(-s // ps) * ps, window) for s in range(lo, hi + 1)})


@dataclasses.dataclass
class Record:
    spec: T.RequestSpec
    due: float                       # absolute, harness clock
    submitted: float = 0.0
    req: object = None               # the scheduler's Request
    stamps: List[float] = dataclasses.field(default_factory=list)

    def first(self) -> Optional[float]:
        return self.stamps[0] if self.stamps else None


@dataclasses.dataclass
class Window:
    t0: float
    end: float
    records: List[Record]
    # engine counter deltas in the window; None for a counter the
    # program does not keep
    counters: Dict[str, Optional[float]]
    host_s: float                    # run_iteration wall minus its spans
    iterations: int
    prefill_tokens: int
    prefill_flops: float
    decode_flops: float
    drain_s: float
    compiles: int                    # traces, compiles and cache loads
    t_stop: float = 0.0
    stalls: Dict[str, list] = dataclasses.field(default_factory=dict)


class Setup:
    """Weights, engine, pinned plan and warm-up for one cell."""

    def __init__(self, cell, seed: int, *,
                 engine_hook: Optional[Callable] = None):
        c, mix = cell.config, cell.traffic
        self.cell, self.seed = cell, seed
        self.arch = cell.arch()
        self.cfg = program_config(self.arch, c)
        t = time.perf_counter()
        self.params = self.arch.make_params(c, seed)
        jax.block_until_ready(self.params)
        self.times = {"weights_s": time.perf_counter() - t}
        t = time.perf_counter()
        self.engine = build_engine(self.cfg, self.params, EngineConfig(
            max_slots=mix["slots"], max_len=mix["max_len"],
            page_size=c["page_size"], ladder=tuple(c["plan"]["ladder"]),
            use_kernel=None, paged_kv=True))
        if engine_hook is not None:
            engine_hook(self.engine)
        self.engine.apply_frontier_point(pinned_point(
            self.engine, c, self.arch.expert_pairs(c)))
        check_plan(self.engine, c)
        self.times["engine_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.buckets = prefill_buckets(c, mix)
        self._warm_up()
        self.times["warm_up_s"] = time.perf_counter() - t
        # what set-up made lives to the end: the collector need not walk
        # it in the window (a full collection's cost is logged)
        t = time.perf_counter()
        gc.collect()
        self.times["full_gc_s"] = time.perf_counter() - t
        gc.freeze()

    def _warm_up(self) -> None:
        """Every prefill bucket this mix can draw, the decode step at the
        cell's slot count, sampling and page release, once each."""
        lo, hi = T.length_ranges(self.cell.traffic)[:2]
        # the first call gets the freshly made KV pool, every later one a
        # pool the last call returned, which jit keys apart: so one
        # short request first, then every bucket against a returned pool
        for group in (self.buckets[:1], self.buckets):
            for b in group:
                self.engine.submit_request(ServeRequest(
                    prompt=np.ones(max(lo, min(b, hi)), np.int32),
                    max_new_tokens=2))
            while self.engine.has_work():
                self.engine.run_iteration(temperature=0.0)

    def device_bytes(self) -> Dict[str, int]:
        """The reckoning of what the engine holds: the train copy by
        where each leaf lives, then the architecture's device parts."""
        out = {"train_copy_device": 0, "train_copy_host": 0}
        for x in jax.tree_util.tree_leaves(self.params):
            out["train_copy_host" if on_host(x) else
                "train_copy_device"] += int(x.nbytes)
        for name, tree in self.arch.device_parts(self.engine).items():
            out[name] = int(sum(x.nbytes for x in
                                jax.tree_util.tree_leaves(tree)))
        return out

    def close(self) -> None:
        self.engine.close()
        self.engine = self.params = None


def on_host(x) -> bool:
    """Whether an array lives in host memory: a NumPy array, or a JAX
    array in one of the host memory kinds."""
    if not isinstance(x, jax.Array):
        return True
    return "host" in (x.sharding.memory_kind or "")


#: JAX's events for a traced function, an XLA compilation and a program
#: loaded from the persistent cache: any of them inside the window is
#: work that warm-up should have done
JAX_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/backend_compile_duration": "compile",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load"}


class _Monitor:
    """While ``active``: JAX's trace, compile and cache-load events, and
    the garbage collector's pauses, each with its time."""

    def __init__(self):
        self.active = False
        self.events: List[tuple] = []
        self.gc_pauses: List[tuple] = []
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        gc.callbacks.append(self._on_gc)

    def start(self) -> None:
        self.events, self.gc_pauses, self.active = [], [], True

    def _on(self, event, duration, **kw):
        if self.active and event in JAX_EVENTS:
            self.events.append((time.perf_counter(), JAX_EVENTS[event],
                                kw.get("fun_name"), duration))

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.active:
            self.gc_pauses.append((self._gc_t0, info["generation"],
                                   time.perf_counter() - self._gc_t0))


_MONITOR: Optional[_Monitor] = None


#: the engine counters the window reads; a program without one reads None
COUNTERS = ("prefill_s", "decode_s", "iterations", "tokens_generated",
            "sample_s", "kv_release_s", "prefill_calls", "prefill_tokens",
            "prefill_padded_tokens", "decode_rows")


def _stats(engine) -> Dict[str, Optional[float]]:
    m = engine.metrics
    return {k: None if m.get(k) is None else float(m[k]) for k in COUNTERS}


def _span_seconds(engine) -> tuple:
    m = engine.metrics
    return m["prefill_s"], m["decode_s"]


def run_window(setup: Setup, seconds: float, *, trace_annotation=None,
               drain_limit_s: float = 120.0) -> Window:
    """Offer the mix's load for ``seconds``, then serve on until every
    request due in the window has its first token."""
    global _MONITOR
    if _MONITOR is None:
        _MONITOR = _Monitor()
    eng, cell, seed = setup.engine, setup.cell, setup.seed
    mix, c = cell.traffic, cell.config
    vocab = c["vocab_size"]
    ann = trace_annotation or (lambda name: contextlib.nullcontext())
    records: List[Record] = []
    live: List[Record] = []
    closed = mix["loop"] == "closed"
    if closed:
        pool = T.closed_loop_specs(mix, seed, vocab)
        pending: List[Record] = []
    else:
        specs = T.open_loop(mix, seed, seconds, vocab)

    def submit(rec: Record, now: float) -> None:
        rid = eng.submit_request(ServeRequest(
            prompt=rec.spec.prompt, max_new_tokens=rec.spec.max_new_tokens))
        rec.req = eng.queue[-1]
        if rec.req.rid != rid:
            raise RuntimeError("the engine's queue did not end with the "
                               "request just submitted")
        rec.submitted = now
        records.append(rec)
        live.append(rec)

    prefill_flops, decode_flops = (setup.arch.prefill_flops,
                                   setup.arch.decode_flops)
    s0 = _stats(eng)
    host_s = 0.0
    n_iter = 0
    pre_tok = 0
    pre_fl = dec_fl = 0.0
    s_end = None
    its: List[tuple] = []   # (start, wall, prefill s, decode s, slept s)
    slept = 0.0
    _MONITOR.start()
    t0 = time.perf_counter()
    end = t0 + seconds
    if closed:
        for _ in range(int(mix["clients"])):
            pending.append(Record(next(pool), t0))
    else:
        pending = [Record(s, t0 + s.due_s) for s in specs]
        pending.reverse()                       # pop() takes the earliest
    with ann("bench.window"):
        while True:
            now = time.perf_counter()
            if closed:
                for rec in pending:
                    submit(rec, now)
                pending = []
            else:
                while pending and pending[-1].due <= now:
                    submit(pending.pop(), now)
            if now >= end:
                if s_end is None:
                    s_end = _stats(eng)
                if all(r.stamps for r in records if r.due < end):
                    break
                if now - end > drain_limit_s:
                    break
            if not eng.has_work():
                if pending:
                    with ann("bench.wait"):
                        time.sleep(max(0.0, pending[-1].due - now))
                    slept += time.perf_counter() - now
                    continue
                if now >= end:
                    break
                continue
            pre0, dec0 = _span_seconds(eng)
            t_it = time.perf_counter()
            with ann("bench.iteration"):
                eng.run_iteration(temperature=0.0)
            t_done = time.perf_counter()
            pre1, dec1 = _span_seconds(eng)
            in_window = t_it < end
            if in_window:
                n_iter += 1
                d_pre, d_dec = pre1 - pre0, dec1 - dec0
                host_s += (t_done - t_it) - d_pre - d_dec
                its.append((t_it, t_done - t_it, d_pre, d_dec, slept))
            still = []
            for rec in live:
                req = rec.req
                have = len(rec.stamps)
                for k in range(have + 1, len(req.out_tokens) + 1):
                    if k == 1:
                        rec.stamps.append(req.t_first)
                        if in_window:
                            pre_tok += len(req.prompt)
                            pre_fl += prefill_flops(c, len(req.prompt))
                    else:
                        rec.stamps.append(t_done)
                        if in_window:
                            dec_fl += decode_flops(c, len(req.prompt) + k - 1)
                if req.t_done is None:
                    still.append(rec)
                elif closed and t_done < end:
                    pending.append(Record(next(pool), t_done))
            live = still
    t_stop = time.perf_counter()
    _MONITOR.active = False
    if s_end is None:
        s_end = _stats(eng)
    counters = {k: None if s0[k] is None or s_end[k] is None
                else s_end[k] - s0[k] for k in s0}
    return Window(t0=t0, end=end, records=records, counters=counters,
                  host_s=host_s, iterations=n_iter, prefill_tokens=pre_tok,
                  prefill_flops=pre_fl, decode_flops=dec_fl,
                  drain_s=max(0.0, t_stop - end),
                  compiles=len(_MONITOR.events), t_stop=t_stop,
                  stalls=_stalls(its, t0, _MONITOR))


def _stalls(its, t0: float, mon: _Monitor, n: int = 4) -> Dict[str, list]:
    """Where the window lost time, for the log: the iterations whose host
    part (wall less the prefill and decode spans), prefill span or decode
    span was longest, the longest pauses between iterations, the
    collector's longest pauses and JAX's events, each at its offset from
    the window's start."""
    def rnd(*xs):
        return [round(x, 4) for x in xs]
    host = sorted(its, key=lambda r: r[1] - r[2] - r[3])[-n:]
    pre = sorted(its, key=lambda r: r[2])[-n:]
    dec = sorted(its, key=lambda r: r[3])[-n:]
    between = sorted(((b[0] - (a[0] + a[1]) - (b[4] - a[4]), a[0] + a[1])
                      for a, b in zip(its, its[1:])))[-n:]
    return {"host_s": [rnd(t - t0, w - p - d) for t, w, p, d, _ in host],
            "prefill_s": [rnd(t - t0, p) for t, w, p, d, _ in pre],
            "decode_s": [rnd(t - t0, d) for t, w, p, d, _ in dec],
            "between_s": [rnd(t - t0, g) for g, t in between],
            "gc_s": [rnd(t - t0, d) + [g] for t, g, d in
                     sorted(mon.gc_pauses, key=lambda r: r[2])[-n:]],
            "jax": [rnd(t - t0, d) + [kind, name]
                    for t, kind, name, d in mon.events[:8]]}


def serve_until_finished(setup: Setup, win: Window, tokens: int,
                         limit_s: float = 60.0) -> float:
    """After the window, serve on without new arrivals until the finished
    requests hold ``tokens`` served tokens, or ``limit_s`` has passed;
    returns the seconds it took."""
    t0 = time.perf_counter()

    def finished() -> int:
        return sum(len(r.req.out_tokens) for r in win.records
                   if r.req.t_done is not None)
    while (finished() < tokens and setup.engine.has_work()
           and time.perf_counter() - t0 < limit_s):
        setup.engine.run_iteration(temperature=0.0)
    return time.perf_counter() - t0
