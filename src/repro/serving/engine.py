"""Adaptive MoE serving engine — continuous batching over fixed decode
slots (the paper's Fig. 1 system + an iteration-level scheduler,
DESIGN.md §3).

Architecture:

  * ``ContinuousScheduler`` (serving/scheduler.py) owns requests: the
    admission queue, per-slot request state, join/retire at EVERY decode
    iteration.
  * this engine owns the model side: one slot-based KV cache of
    ``max_slots`` rows, a jitted decode step specialized ONCE for the full
    slot count (idle slots ride along masked by position=-1), and
    per-bucket jitted prefill-into-slot functions so a new request joins a
    live batch without recompiling or re-padding it.
  * the runtime expert path: non-resident experts under the active
    ``PrecisionPlan`` are fetched through the real
    ``ExpertCache``/``PrefetchingExpertCache`` (core/expert_cache.py) from
    the routed expert ids of every decode iteration. ``metrics`` reports
    the MEASURED ``transfer_s``/``miss_rate_measured`` next to the
    retained analytical ``transfer_s_est``/``miss_rate`` so the cost model
    is cross-validated on every run.
  * ASYNC OVERLAP mode (``EngineConfig(overlap=True)``, DESIGN.md §12):
    staging moves to an ``AsyncExpertCache`` worker pool and the decode
    step runs through the model's per-layer hooks as a lookahead
    pipeline — while layer L computes, layer L+1's predicted experts
    (the previous iteration's captured routes: decode re-demands most of
    them for adjacent tokens) stage in the background; each layer's
    ACTUAL routed demand is then awaited, exposing only what prediction
    could not hide. ``metrics`` splits the transfer time into
    ``transfer_exposed_s`` (blocked the critical path) and
    ``transfer_overlapped_s`` (hidden under compute); throughput charges
    only the exposed part. The sync path survives as ``overlap=False``
    for A/B comparison, and ``close()`` joins the transfer workers.

Fidelity (DESIGN.md §2): model compute is REAL (jitted decode with the
plan's per-rung banks; on a TPU the banks run through the Pallas
kernels). Expert streaming runs through the real LRU cache with real
``device_put`` staging, but the step does not read the staged copies:
the serve banks hold EVERY expert on the device, host-tier experts
included, whatever the plan's placement. Transfers are measured, not
consumed by the matmuls, and device memory holds the whole model.

Reconfiguration is safe mid-flight: placement-only replans apply between
decode iterations without touching in-flight requests (placement never
changes outputs — tested); a bank-split change first DRAINS the active
slots (finishing their requests, admitting no new ones), then
re-specializes the step functions — the paper's "minimal downtime" path,
measured in ``metrics["reconfig_s"]``.

The DECLARATIVE entry points (DESIGN.md §9) are ``apply_target`` (resolve
a ``QoSTarget`` on the engine's ``ParetoFrontier`` and apply the selected
point) and ``apply_frontier_point`` (the ``QoSController``'s walk step);
the imperative ``configure(mem_budget_bytes, preference, num_q)`` is a
deprecated shim that builds a ``QoSTarget`` internally.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cost_model import (HardwareModel, estimate_qos,
                                   expert_access_stats, hardware_for_device,
                                   kv_bytes_bucketed, kv_token_bytes)
from repro.core.expert_cache import (AsyncExpertCache, ExpertCache,
                                     PrefetchingExpertCache)
from repro.core.mixed_moe import serve_shardings
from repro.core.pareto import FrontierPoint, ParetoFrontier, QoSTarget
from repro.core.planner import AdaptivePlanner, PlanResult
from repro.core.precision_plan import (DEVICE, HOST, PrecisionPlan,
                                       quantized_rungs)
from repro.kernels.ops import serve_through_kernels
from repro.models.model import Model, apply_precision_plan, build_model
from repro.serving.api import EngineConfig, ServeRequest, ServeResult
from repro.serving.metrics import base_metrics
from repro.serving.paged_kv import PageAllocator
from repro.serving.sampler import sample, speculative_verify
from repro.serving.scheduler import (ContinuousScheduler, Request,
                                     RequestSLO, SamplingParams,
                                     SchedulerConfig)
from repro.serving.tracing import span

__all__ = ["AdaptiveServingEngine", "Request", "measure_host_link_bw"]

# per-process cache: the engine is constructed once per test/benchmark
# point, and a 16 MiB device_put probe per construction both slows the
# suite and skews short benchmarks. Keyed by probe size.
_HOST_LINK_BW_CACHE: Dict[int, float] = {}


def measure_host_link_bw(nbytes: int = 1 << 24, *,
                         refresh: bool = False) -> float:
    """Measured device_put bandwidth (host->device), B/s. Cached per
    process (the link does not change under our feet); ``refresh=True``
    forces a re-probe."""
    if not refresh and nbytes in _HOST_LINK_BW_CACHE:
        return _HOST_LINK_BW_CACHE[nbytes]
    buf = np.ones(nbytes, np.uint8)
    dev = jax.devices()[0]
    jax.block_until_ready(jax.device_put(buf[:1024], dev))  # warm
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(buf, dev))
    bw = nbytes / max(time.perf_counter() - t0, 1e-9)
    _HOST_LINK_BW_CACHE[nbytes] = bw
    return bw


def _bucket(n: int, lo: int = 8, hi: Optional[int] = None) -> int:
    """Next power-of-two >= n: bounds prefill recompiles to log(max_len).
    ``hi`` clamps to the KV-cache window so a prompt near ``max_len``
    can't request a bucket wider than the cache (the prompt itself was
    already validated to fit by the scheduler)."""
    b = lo
    while b < n:
        b *= 2
    return b if hi is None else min(b, hi)


class AdaptiveServingEngine:
    """Continuous-batching adaptive engine.

    Preferred construction is the typed surface (DESIGN.md §9):
    ``AdaptiveServingEngine(cfg, params, config=EngineConfig(...))`` or
    ``repro.serving.api.build_engine``. The flat keyword arguments
    (``max_batch`` — the number of decode slots —, ``max_len``, ...) are
    the backward-compatible spelling and populate an ``EngineConfig``
    internally."""

    def __init__(self, cfg: ModelConfig, params, *, mesh=None,
                 config: Optional[EngineConfig] = None,
                 hw: Optional[HardwareModel] = None,
                 max_batch: int = 8, max_len: int = 256,
                 use_kernel: Optional[bool] = None,
                 max_active_tokens: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 swap_bytes: Optional[int] = None,
                 prefetch: bool = False,
                 expert_cache=None):
        if cfg.moe is None:
            raise ValueError("the adaptive engine serves MoE models")
        if config is None:
            config = EngineConfig(
                max_slots=max_batch, max_len=max_len,
                use_kernel=use_kernel,
                max_active_tokens=max_active_tokens, max_queue=max_queue,
                swap_bytes=swap_bytes, prefetch=prefetch, hw=hw)
        if config.ladder is not None:
            # the deployment declares its precision ladder on the typed
            # surface; it overrides the config default (DESIGN.md §11)
            cfg = cfg.replace(mop=dataclasses.replace(
                cfg.mop, ladder=tuple(config.ladder)))
        self.config = config
        self.cfg = cfg
        self.params_train = params        # train-layout master copy
        self.mesh = mesh
        self.max_slots = config.max_slots
        self.max_len = config.max_len
        self.use_kernel = (serve_through_kernels()
                           if config.use_kernel is None
                           else config.use_kernel)
        if config.hw is not None:
            # an explicit hardware model wins, but an explicit
            # overlap_efficiency knob still applies on top (it would
            # otherwise be silently dropped and the frontier would rank
            # under the additive model while the pipeline runs)
            self.hw = config.hw
            if config.overlap_efficiency is not None:
                self.hw = dataclasses.replace(
                    self.hw,
                    overlap_efficiency=float(config.overlap_efficiency))
        else:
            # overlap mode seeds the analytic overlap window (refined at
            # runtime by calibrate_overlap, DESIGN.md §12); sync keeps
            # the additive model exactly.
            eff = config.overlap_efficiency
            if eff is None:
                eff = 0.85 if config.overlap else 0.0
            self.hw = hardware_for_device(
                jax.devices()[0], host_link_bw=measure_host_link_bw(),
                overlap_efficiency=float(eff))
        self.planner = AdaptivePlanner(cfg, hw=self.hw,
                                       ep=getattr(config, "ep", 1))
        self.model: Model = build_model(cfg, mesh,
                                        use_kernel=self.use_kernel)
        if self.model.prefill_into_slot is None:
            raise ValueError(f"{cfg.arch_id}: family {cfg.family} has no "
                             "slot-cache decode path")
        # KV cache: paged (fixed-size pages + per-slot page table,
        # DESIGN.md §13) by default; the fully-windowed slot cache
        # survives as the A/B baseline (paged_kv=False). Decode through
        # the pages is bit-identical to the slot cache (tested).
        self.paged = bool(config.paged_kv
                          and self.model.paged_decode_step_routed
                          is not None)
        max_active = config.max_active_tokens
        self._kv_token_bytes = kv_token_bytes(cfg)
        if self.paged:
            self.kv_pool, self.kv_meta = self.model.init_paged_cache(
                self.max_slots, self.max_len,
                page_size=config.page_size,
                num_pages=config.kv_pool_pages)
            self.window = self.kv_meta.window
            self.kv_alloc = PageAllocator(
                self.max_slots, self.kv_meta.chunks_per_slot,
                self.kv_meta.num_pages, self.kv_meta.page_size)
            self.cache = None
            worst = self.max_slots * self.kv_meta.chunks_per_slot
            if self.kv_meta.num_pages - 1 < worst:
                # sub-worst-case pool: cap admitted tokens so ensure()
                # can never dead-end mid-flight (per-slot ceil rounding
                # costs at most one page each, hence the max_slots term)
                derived = (self.kv_meta.num_pages - 1 - self.max_slots) \
                    * self.kv_meta.page_size
                max_active = derived if max_active is None \
                    else min(max_active, derived)
        else:
            self.kv_pool = self.kv_meta = self.kv_alloc = None
            self.cache = self.model.init_cache(self.max_slots,
                                               self.max_len)
            self.window = int(self.cache["k"].shape[2])
        self.scheduler = ContinuousScheduler(SchedulerConfig(
            max_slots=self.max_slots, max_len=self.max_len,
            max_prompt_len=self.window,
            max_active_tokens=max_active,
            max_queue=config.max_queue))
        # runtime expert streaming: host master store + device LRU swap.
        # A multi-tenant deployment passes a tenant-scoped VIEW of the
        # shared swap space instead (core/expert_cache.py, DESIGN.md §10)
        # — same interface, namespaced keys, jointly shared byte budget.
        self._swap_bytes = config.swap_bytes
        self._owns_cache = expert_cache is None
        if expert_cache is not None:
            if config.prefetch and not hasattr(expert_cache, "hint"):
                raise ValueError(
                    "EngineConfig(prefetch=True) needs an expert cache "
                    "with hint() support; the provided shared view has "
                    "none")
            if config.overlap and not getattr(expert_cache, "is_async",
                                              False):
                raise ValueError(
                    "EngineConfig(overlap=True) needs an async expert "
                    "cache (AsyncExpertCache, or a scoped view of one — "
                    "DESIGN.md §12); the provided cache stages "
                    "synchronously")
            self.expert_cache = expert_cache
            if hasattr(expert_cache, "bind_fetch"):
                expert_cache.bind_fetch(self._fetch_expert)
        else:
            cache_cls = AsyncExpertCache if config.overlap \
                else (PrefetchingExpertCache if config.prefetch
                      else ExpertCache)
            self.expert_cache = cache_cls(
                self._fetch_expert,
                capacity_bytes=config.swap_bytes
                or 4 * max(cfg.expert_param_bytes(16), 1))
        self._prefetch = config.prefetch and hasattr(self.expert_cache,
                                                     "hint")
        # per-layer lookahead pipeline: overlap mode + the model's
        # per-layer decode hooks (DESIGN.md §12)
        self._pipeline = bool(config.overlap
                              and self.model.decode_layer_routed
                              is not None)
        self._prev_demanded: List[Tuple[int, int]] = []
        #: pipelined mode's per-layer prediction: the previous
        #: iteration's demanded (non-resident) keys, layer-indexed
        self._prev_layer_keys: Optional[List[List[Tuple[int, int]]]] = None
        #: accumulated routed-access histogram [L, E] over TRUE expert
        #: ids (bank slots mapped back through the plan's expert order) —
        #: the dynamic precision controller's traffic signal (DESIGN.md
        #: §15). Deliberately NOT reset by ``_reconfigure``: the
        #: histogram must survive (placement-only) replans; callers
        #: window it via ``reset_route_counts()`` / their own snapshots.
        self.route_counts: np.ndarray = np.zeros(
            (cfg.num_moe_layers, cfg.moe.num_experts if cfg.moe else 0),
            np.int64)
        self._host_store: Dict[Tuple[int, int], Any] = {}
        self._resident: set = set()
        self._miss_bytes_per_tok = 0.0
        self._order: Optional[np.ndarray] = None   # bank slot -> expert id
        self._serve_params = None
        self._plan_result: Optional[PlanResult] = None
        self._frontier: Optional[ParetoFrontier] = None
        self._target: Optional[QoSTarget] = None
        self._active_point: Optional[FrontierPoint] = None
        self._compiled: Dict[Any, Any] = {}
        self._key = jax.random.key(0)
        # ladder-draft self-speculative decoding (DESIGN.md §17): draft
        # depth K per iteration; 0 = plain decode, byte-identical to the
        # pre-speculation engine. Draft params (every expert at the
        # LOWEST ladder rung) build lazily on first speculative
        # iteration and survive replans (they depend only on the ladder
        # and group size, not on the serving rung assignment).
        self.speculate_k = max(0, int(getattr(config, "speculate", 0)
                                      or 0))
        self._draft_params = None
        self._draft_sig: Optional[Tuple] = None
        # the shared sim/real metric schema (repro.serving.metrics,
        # DESIGN.md §14.2) — controllers see the same dict shape against
        # the deterministic SimulatedEngine. KV notes (DESIGN.md §13):
        # "kv_allocated_bytes" is what the cache layout holds (mapped
        # pages for paged; slots x window always for the slot cache),
        # "kv_used_bytes" the valid cached tokens — their gap is the
        # padding waste the paged cache eliminates; the *_byte_iters
        # sums give run averages.
        self.metrics: Dict[str, Any] = base_metrics()
        self.metrics["kv_capacity_bytes"] = (
            (self.kv_meta.num_pages - 1) * self.kv_meta.page_size
            * self._kv_token_bytes if self.paged
            else kv_bytes_bucketed(cfg, self.max_slots, self.window))

    # ------------------------------------------------------------------
    # Compatibility surface
    # ------------------------------------------------------------------
    @property
    def queue(self):
        return self.scheduler.queue

    @property
    def done(self) -> Dict[int, Request]:
        return self.scheduler.done

    @property
    def max_batch(self) -> int:
        return self.max_slots

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    # ------------------------------------------------------------------
    # Planner integration / mid-flight reconfiguration
    # ------------------------------------------------------------------
    @property
    def frontier(self) -> ParetoFrontier:
        """The engine's Pareto frontier over the MoP config space
        (DESIGN.md §9), built lazily once per (hardware model, slot
        count) and shared with the QoSController."""
        if self._frontier is None:
            self._frontier = self.planner.frontier(
                batch_size=self.max_slots)
        return self._frontier

    @property
    def target(self) -> Optional[QoSTarget]:
        """The active declarative target (set by ``apply_target`` or the
        ``configure`` shim)."""
        return self._target

    @property
    def active_point(self) -> Optional[FrontierPoint]:
        """The frontier point currently applied; None when the active
        plan came through the imperative shim (possibly off-frontier)."""
        return self._active_point

    def apply_target(self, target: QoSTarget) -> FrontierPoint:
        """Declarative reconfiguration (DESIGN.md §9): resolve ``target``
        on the frontier and apply the selected point via the mid-flight
        replan path. Raises
        :class:`~repro.core.pareto.InfeasibleTarget` when the hard
        constraints admit no configuration."""
        if self.config.kv_reserve:
            # paged KV reserve (DESIGN.md §13): HBM a sub-worst-case page
            # pool reclaims vs the bucketed slot cache widens the expert
            # residency budget the frontier resolves against
            target = target.with_kv_reclaimed(self.kv_reclaimed_bytes())
        point = self.frontier.select(target)
        self._target = target
        self.apply_frontier_point(point)
        return point

    def kv_reclaimed_bytes(self) -> int:
        """HBM the paged pool reclaims vs the fully-windowed slot cache
        (0 for the slot cache or a worst-case-sized pool)."""
        if not self.paged:
            return 0
        bucketed = kv_bytes_bucketed(self.cfg, self.max_slots, self.window)
        return max(0, bucketed - int(self.metrics["kv_capacity_bytes"]))

    def apply_frontier_point(self, point: FrontierPoint) -> PlanResult:
        """Apply one frontier point (the QoSController's walk step).
        Frontier plans are bit-identical to planner plans for the same
        knobs, so this routes through the ordinary replan path: the
        point's exact device footprint is the budget, the point's
        per-rung counts are the quality knobs (a multi-rung point is not
        expressible through Num_E4 alone — DESIGN.md §11), and surplus
        HBM is returned to the pool."""
        counts = point.quantized_counts() if point.counts_per_rung \
            else None
        peer = int(getattr(point, "peer_experts", 0) or 0)
        if peer or self.planner.ep > 1:
            # EP apply path (DESIGN.md §16): pin the point's exact
            # (total resident, peer) split — the budget-derived
            # residency cannot reconstruct a peer slice. Single-device
            # points keep the historical budget-derived path untouched.
            result = self._reconfigure(
                float(point.qos.device_bytes), "quality",
                point.num_q_experts, counts=counts,
                resident_experts=point.resident_experts,
                peer_experts=peer)
        else:
            result = self._reconfigure(float(point.qos.device_bytes),
                                       "quality", point.num_q_experts,
                                       counts=counts)
        self._active_point = point
        return result

    def configure(self, mem_budget_bytes: float, preference: str,
                  num_q_experts: Optional[int] = None) -> PlanResult:
        """DEPRECATED imperative shim (use ``apply_target``): builds the
        equivalent ``QoSTarget`` — "as fast as possible inside the
        budget" for the throughput preference, "this quality level inside
        the budget" for the quality preference — records it as the active
        target, and replans through the legacy eq.(1) path so existing
        callers see bit-identical plans."""
        warnings.warn(
            "AdaptiveServingEngine.configure() is deprecated; declare a "
            "QoSTarget and use apply_target() (DESIGN.md §9)",
            DeprecationWarning, stacklevel=2)
        if preference == "throughput":
            self._target = QoSTarget(mem_budget_bytes=mem_budget_bytes,
                                     min_tokens_per_s=math.inf)
        else:
            loss = None
            if num_q_experts is not None:
                from repro.core.cost_model import RUNG_QUALITY_COST
                from repro.core.precision_plan import quantized_rungs
                frac = num_q_experts / max(self.planner.num_experts_total,
                                           1)
                # legacy shim: Num_E4 counts experts at the LOWEST rung
                low = quantized_rungs(self.planner.ladder)[0]
                per_bit = RUNG_QUALITY_COST.get(low, 0.07)
                loss = per_bit * min(max(frac, 0.0), 1.0)
            self._target = QoSTarget(mem_budget_bytes=mem_budget_bytes,
                                     max_quality_loss=loss)
        result = self._reconfigure(mem_budget_bytes, preference,
                                   num_q_experts)
        self._active_point = None    # imperative plans may be off-frontier
        return result

    def _reconfigure(self, mem_budget_bytes: float, preference: str,
                     num_q_experts: Optional[int] = None,
                     counts=None, resident_experts: Optional[int] = None,
                     peer_experts: Optional[int] = None) -> PlanResult:
        """Replan under new constraints; safe to call with requests in
        flight. Placement-only changes apply immediately (between decode
        iterations); a bank-split change drains the active slots first."""
        t0 = time.perf_counter()
        # async staging barrier (DESIGN.md §12): every enqueued transfer
        # must land BEFORE the plan changes, or a stale-plan blob could
        # be admitted after the invalidate below (no-op for sync caches)
        self.expert_cache.drain()
        result, delta = self.planner.replan(
            mem_budget_bytes, preference, num_q_experts,
            batch_size=self.max_slots, counts=counts,
            resident_experts=resident_experts,
            peer_experts=peer_experts)
        plan = result.plan
        prev_plan = self._plan_result.plan \
            if self._plan_result is not None else None
        sig = plan.bank_sizes()
        rebuild = (prev_plan is None
                   or prev_plan.bank_sizes() != sig
                   or prev_plan.seed != plan.seed)
        drain_s = 0.0
        if rebuild:
            if self.scheduler.num_active:
                # graceful drain: finish in-flight requests on the OLD
                # banks; the queue holds until the new plan is live. The
                # drain is ordinary decoding (counted in decode_s/drain_s),
                # NOT reconfiguration downtime.
                self.metrics["drains"] += 1
                t_drain = time.perf_counter()
                while self.scheduler.num_active:
                    self.run_iteration(admit=False)
                drain_s = time.perf_counter() - t_drain
                self.metrics["drain_s"] += drain_s
                # the drain iterations enqueued fresh async fetches on
                # the OLD plan — barrier again before invalidating
                self.expert_cache.drain()
            # bank split changed -> re-specialize the step functions
            self._serve_params = self._build_serve_params(plan)
            self._compiled.clear()
            self._host_store.clear()
            self.expert_cache.invalidate()
        self._plan_result = result
        self._order = plan.expert_order()
        # accelerator-resident = LOCAL + PEER (DESIGN.md §16): under EP
        # the banks are physically sharded over the mesh, so a PEER
        # expert is served by the all2all dispatch, never streamed over
        # the host link. Single-device plans have no PEER entries, so
        # this is the historical DEVICE mask bit-for-bit.
        newly_resident = {
            (li, ei) for li, ei in np.argwhere(plan.location != HOST)}
        if not rebuild:
            # Same bank shapes does NOT imply the same bits ASSIGNMENT:
            # an earlier apply_bits_update may have swapped rungs between
            # experts, while the planner's fresh plan carries the
            # canonical assignment for these counts. Banks and staged
            # host blobs must follow the new assignment or stale-rung
            # weights get served (shapes unchanged, so no recompile).
            rung_changed = set()
            if (prev_plan.bits != plan.bits).any():
                self._serve_params = self._build_serve_params(plan)
                rung_changed = {
                    (int(l), int(e)) for l, e in
                    np.argwhere(prev_plan.bits != plan.bits)}
                for k in list(self._host_store):
                    if (k[0], k[1]) in rung_changed:
                        del self._host_store[k]
            # placement-only: swap entries that moved on-device are now
            # HBM-resident — drop them from the swap cache, along with
            # any entry staged at a rung the new plan no longer assigns
            self.expert_cache.invalidate(
                [k for k in self.expert_cache.resident_keys()
                 if k[:2] in newly_resident or k[:2] in rung_changed])
        self._resident = newly_resident
        self._prev_demanded = []     # stale-plan hints must not re-stage
        self._prev_layer_keys = None
        hit, self._miss_bytes_per_tok = expert_access_stats(self.cfg, plan)
        self.metrics["miss_rate"] = 1.0 - hit
        downtime = time.perf_counter() - t0 - drain_s
        self.metrics["reconfig_s"] += downtime
        self.metrics["reconfigs"] += 1
        if delta is not None:
            # partial-reconfiguration report (DESIGN.md §10.3): only the
            # diffed experts migrate; everything else stays in place
            self.metrics["last_delta_traffic_gib"] = \
                delta["traffic_bytes"] / 2**30
            self.metrics["last_migrated_experts"] = len(delta["migrated"])
            self.metrics["last_migrated_bytes"] = delta["traffic_bytes"]
            self.metrics["last_reconfig_downtime_s"] = downtime
            self.metrics["migrated_bytes_total"] = \
                self.metrics.get("migrated_bytes_total", 0) \
                + delta["traffic_bytes"]
        return result

    def _build_serve_params(self, plan: PrecisionPlan):
        """The plan's serve-layout params. On an EP mesh they are placed
        on it here, banks sharded as the step reads them, so no step
        moves weights; a single-device engine builds them where it
        runs."""
        params = apply_precision_plan(self.params_train, self.cfg, plan)
        if self.mesh is None:
            return params
        return jax.device_put(params, serve_shardings(
            params, self.cfg.moe, self.mesh))

    # ------------------------------------------------------------------
    # Dynamic precision (DESIGN.md §15)
    # ------------------------------------------------------------------
    @property
    def serve_params(self):
        """The params the step runs on: the active plan's per-rung banks
        plus the shared non-expert weights (None before the first
        replan)."""
        return self._serve_params

    @property
    def current_plan(self) -> Optional[PrecisionPlan]:
        """The active precision plan (None before the first replan)."""
        return self._plan_result.plan if self._plan_result is not None \
            else None

    def reset_route_counts(self) -> None:
        """Zero the accumulated routing histogram (callers that window
        it — like the dynamic controller — snapshot instead)."""
        self.route_counts[...] = 0

    def apply_bits_update(self, new_bits: np.ndarray) -> Dict[str, Any]:
        """In-place rung flips (DESIGN.md §15): same expert locations,
        same per-layer rung counts, only the bits[L, E] ASSIGNMENT
        changes. This is the :class:`DynamicPrecisionController`'s apply
        path — diff-only, no planner replan, no drain, no recompile:

        * bank shapes are unchanged (per-layer rung counts preserved by
          contract), so the jitted step functions stay specialized and
          only the serve-layout banks + router permutation rebuild;
        * flipped experts resident in the swap cache are re-staged at
          their new rung through ``ExpertCache.update()``, which charges
          exactly the byte delta (byte-conservation is tested).

        Returns a report dict: flipped/promotions/demotions counts, the
        summed cache byte delta, and the number of re-staged entries.
        """
        assert self._plan_result is not None, "no active plan"
        old_plan = self._plan_result.plan
        new_bits = np.asarray(new_bits, old_plan.bits.dtype)
        if new_bits.shape != old_plan.bits.shape:
            raise ValueError(f"bits shape {new_bits.shape} != "
                             f"{old_plan.bits.shape}")
        for b in np.unique(new_bits).tolist():
            if int(b) not in old_plan.ladder:
                raise ValueError(f"rung {b} not on ladder "
                                 f"{old_plan.ladder}")
        for li in range(new_bits.shape[0]):
            for b in old_plan.ladder:
                if int((new_bits[li] == b).sum()) \
                        != int((old_plan.bits[li] == b).sum()):
                    raise ValueError(
                        "apply_bits_update must preserve per-layer rung "
                        f"counts (layer {li}, rung {b}): a count change "
                        "is a bank split — use apply_frontier_point")
        flipped = new_bits != old_plan.bits
        promotions = int((new_bits > old_plan.bits).sum())
        demotions = int((new_bits < old_plan.bits).sum())
        report: Dict[str, Any] = {
            "flipped": int(flipped.sum()), "promotions": promotions,
            "demotions": demotions, "cache_bytes_delta": 0,
            "restaged": 0,
        }
        if not report["flipped"]:
            return report
        t0 = time.perf_counter()
        # async staging barrier: in-flight transfers carry OLD-rung blobs
        self.expert_cache.drain()
        new_plan = dataclasses.replace(old_plan, bits=new_bits)
        # same bank shapes -> the jitted step functions stay valid; only
        # the bank contents and the router permutation change
        self._serve_params = self._build_serve_params(new_plan)
        self._plan_result = dataclasses.replace(
            self._plan_result, plan=new_plan,
            qos=estimate_qos(self.cfg, new_plan, self.planner.hw,
                             self.max_slots, self.planner.profile))
        # keep the planner's replan diffing anchored on the live plan
        self.planner.current = self._plan_result
        self._order = new_plan.expert_order()
        flipped_keys = {(int(l), int(e)) for l, e in np.argwhere(flipped)}
        for k in list(self._host_store):
            if (k[0], k[1]) in flipped_keys:
                del self._host_store[k]     # re-quantize at the new rung
        for key in list(self.expert_cache.resident_keys()):
            if (key[0], key[1]) in flipped_keys:
                report["cache_bytes_delta"] += \
                    self.expert_cache.update(key, self._fetch_expert(key))
                report["restaged"] += 1
        hit, self._miss_bytes_per_tok = expert_access_stats(self.cfg,
                                                            new_plan)
        self.metrics["miss_rate"] = 1.0 - hit
        self.metrics["reconfig_s"] += time.perf_counter() - t0
        self.metrics["bits_updates"] = \
            self.metrics.get("bits_updates", 0) + 1
        self.metrics["rung_flips"] = \
            self.metrics.get("rung_flips", 0) + report["flipped"]
        return report

    # ------------------------------------------------------------------
    # Request lifecycle
    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16, *,
               sampling: Optional[SamplingParams] = None,
               slo: Optional[RequestSLO] = None,
               now: Optional[float] = None) -> int:
        """Submit a request; forwards sampling/SLO/arrival-time to the
        scheduler's richer ``submit`` (admission is by priority with
        deadline-aware ordering — DESIGN.md §9)."""
        return self.scheduler.submit(prompt, max_new_tokens, now,
                                     sampling=sampling, slo=slo)

    def submit_request(self, request: ServeRequest) -> int:
        """Typed-surface spelling of ``submit`` (serving/api.py)."""
        return self.submit(request.prompt, request.max_new_tokens,
                           sampling=request.sampling, slo=request.slo)

    def result(self, rid: int) -> ServeResult:
        """The ServeResult of a completed request (KeyError while the
        request is queued or in flight)."""
        return ServeResult.from_request(self.scheduler.done[rid])

    def _jit(self, name, fn, donate=()):
        """The jitted ``fn``, cached under ``name`` (a string, or a tuple
        led by one). Its program is named ``engine_<name>``, so that a
        profiler trace tells the engine's programs apart."""
        if name not in self._compiled:
            def program(*args):
                return fn(*args)
            program.__name__ = program.__qualname__ = "engine_" + (
                name[0] if isinstance(name, tuple) else name)
            self._compiled[name] = jax.jit(program, donate_argnums=donate)
        return self._compiled[name]

    # -- expert streaming ----------------------------------------------
    def _fetch_expert(self, key):
        """Host loader for the expert swap cache: the expert's weights in
        the precision RUNG the active plan assigns it (packed int4/int8 +
        scales or bf16), staged from the train-layout master copy."""
        li, ei = key[0], key[1]
        blob = self._host_store.get((li, ei))
        if blob is None:
            moe_p = self.params_train["layers"]["moe"]
            w = {k: np.asarray(moe_p[k][li, ei])
                 for k in ("w_gate", "w_up", "w_down")}
            bits = int(self._plan_result.plan.bits[li, ei])
            if bits < 16:
                from repro.core.quantization import quantize
                gs = self._plan_result.plan.group_size
                blob = {}
                for k, v in w.items():
                    qt = quantize(jnp.asarray(v), bits, gs)
                    blob[k] = {"q": np.asarray(qt.q),
                               "scales": np.asarray(qt.scales)}
            else:
                blob = w
            self._host_store[(li, ei)] = blob
        return blob

    def _stream_experts(self, route_ids: np.ndarray, rows: List[int]):
        """Feed the routed (layer, expert) accesses of one decode
        iteration through the runtime cache; resident experts are HBM
        hits, the rest go through the LRU swap space.

        Metric semantics: ``miss_rate`` (analytic) assumes every
        non-resident access streams (the paper's memoryless model);
        ``miss_rate_measured`` counts accesses that actually transferred —
        LRU swap hits don't stream, so measured < estimated quantifies the
        temporal locality the paper's uniform-routing model ignores.
        Caveat at smoke scale: ``transfer_s`` can exceed ``transfer_s_est``
        because the bandwidth term is calibrated on a bulk transfer while
        smoke-scale experts are small enough that per-``device_put``
        latency dominates; at paper-scale expert sizes (hundreds of MB)
        the bandwidth term is the honest model."""
        st = self.expert_cache.stats
        blocked0 = st.transfer_s + st.prefetch_s
        if self._prefetch and self._prev_demanded:
            # temporal-locality prefetch BEFORE this iteration's demand:
            # decode re-demands most of the previous iteration's experts
            # (same requests, adjacent tokens); anything evicted since is
            # re-staged speculatively so the demand below hits.
            self.expert_cache.hint(self._prev_demanded)
        order = self._order
        demanded = set()
        for li in range(route_ids.shape[0]):
            for b in rows:
                for slot_id in route_ids[li, b]:
                    ei = int(order[li, int(slot_id)])
                    demanded.add((li, ei))
                    self.route_counts[li, ei] += 1
        misses0 = st.misses
        for key in sorted(demanded):
            self.metrics["expert_accesses"] += 1
            if key in self._resident:
                continue
            self.expert_cache.get(key)
        self.metrics["expert_fetches"] += st.misses - misses0
        self._prev_demanded = [k for k in sorted(demanded)
                               if k not in self._resident]
        # serial staging blocks the critical path for every transferred
        # second (speculative hints included) — all of it is EXPOSED
        self.metrics["transfer_exposed_s"] += \
            st.transfer_s + st.prefetch_s - blocked0
        self._finish_stream_metrics()

    def _finish_stream_metrics(self):
        """Fold the cache's counters into engine metrics. ``transfer_s``
        is DEMAND transfer only (speculative staging reports separately
        as ``prefetch_s`` — DESIGN.md §12); ``transfer_overlapped_s`` is
        the transferred time that did NOT block the critical path."""
        st = self.expert_cache.stats
        self.metrics["transfer_s"] = st.transfer_s
        self.metrics["prefetch_s"] = st.prefetch_s
        self.metrics["transfer_overlapped_s"] = max(
            st.transfer_s + st.prefetch_s
            - self.metrics["transfer_exposed_s"], 0.0)
        if self.metrics["expert_accesses"]:
            self.metrics["miss_rate_measured"] = \
                self.metrics["expert_fetches"] \
                / self.metrics["expert_accesses"]

    def _decode_pipelined(self, toks, pos, rows):
        """Per-layer lookahead pipeline (DESIGN.md §12): while layer L
        computes, layer L+1's PREDICTED experts (the previous iteration's
        captured routes for that layer) stage on the async cache's
        workers; each layer's ACTUAL routed demand is then awaited, so
        only the transfer time prediction could not hide is exposed.
        Numerically identical to the scanned decode step (tested
        bit-exact). Returns the next-token logits (B, V).

        Exposed-time semantics: ``transfer_exposed_s`` is BLOCKED
        WALL-CLOCK, so on a cold host store it also covers the demand
        fetch's host-side staging (extraction + quantization), which the
        sync path counts in no metric (only its ``engine.stream_experts``
        span holds it) — exposed can then exceed the
        device-transfer counters and ``transfer_overlapped_s`` clamps to
        0. The host store is warm after first touch per (expert, plan),
        so at steady state exposed converges to true transfer waits;
        calibrate_overlap() should run on a warm store (same spirit as
        the smoke-scale transfer_s vs transfer_s_est caveat above)."""
        m, params = self.model, self._serve_params
        cache = self.expert_cache
        st = cache.stats
        embed = self._jit("decode_embed", m.decode_embed)
        # the cache argument is DONATED: each per-layer call rebinds
        # self.cache (or the paged pool), so XLA aliases the per-layer
        # update in place instead of copying the whole multi-layer KV
        # cache L times per token (nothing else holds the old buffer)
        if self.paged:
            layer_fn = self._jit(
                "decode_layer_paged", functools.partial(
                    m.paged_decode_layer_routed, window=self.window),
                donate=(1,))
            pt_dev = jnp.asarray(self.kv_alloc.table)
        else:
            layer_fn = self._jit("decode_layer", m.decode_layer_routed,
                                 donate=(1,))
        finish = self._jit("decode_logits", m.decode_logits)
        pos_j = jnp.asarray(pos)
        n_layers = self.cfg.num_layers
        predicted = self._prev_layer_keys
        misses0 = st.misses
        exposed = 0.0
        t_loop0 = time.perf_counter()
        x = embed(params, jnp.asarray(toks))
        if predicted is not None and n_layers:
            cache.prefetch(predicted[0])
        new_layer_keys: List[List[Tuple[int, int]]] = []
        for li in range(n_layers):
            if self.paged:
                x, self.kv_pool, ids = layer_fn(
                    params, self.kv_pool, pt_dev, x, pos_j, jnp.int32(li))
            else:
                x, self.cache, ids = layer_fn(params, self.cache, x,
                                              pos_j, jnp.int32(li))
            if predicted is not None and li + 1 < n_layers:
                # lookahead: stage layer li+1's predicted demand while
                # layer li's compute is still in flight
                cache.prefetch(predicted[li + 1])
            ids_np = np.asarray(ids)       # blocks on layer li's compute
            order = self._order[li]
            np.add.at(self.route_counts[li],
                      order[ids_np[rows].astype(np.int64).ravel()], 1)
            demanded = sorted({(li, int(order[int(s)]))
                               for b in rows for s in ids_np[b]})
            self.metrics["expert_accesses"] += len(demanded)
            need = [k for k in demanded if k not in self._resident]
            t0 = time.perf_counter()
            cache.wait(need)
            exposed += time.perf_counter() - t0
            new_layer_keys.append(need)
        logits = finish(params, x)
        jax.block_until_ready(logits)
        t_loop = time.perf_counter() - t_loop0
        self.metrics["decode_s"] += max(t_loop - exposed, 0.0)
        self.metrics["transfer_exposed_s"] += exposed
        self.metrics["expert_fetches"] += st.misses - misses0
        self._prev_layer_keys = new_layer_keys
        self._finish_stream_metrics()
        return logits

    # -- iteration-level serving ----------------------------------------
    @staticmethod
    def _sampling_of(req: Request, default_temperature: float
                     ) -> Tuple[float, int]:
        """(temperature, top_k) for a request: its own SamplingParams win
        over the engine-level default."""
        if req.sampling is not None:
            return req.sampling.temperature, req.sampling.top_k
        return default_temperature, 0

    def _prefill_slot(self, slot: int, req: Request,
                      temperature: float) -> Optional[int]:
        """Join ``req`` into ``slot``; returns its rid if it already
        retired (max_new_tokens == 1 — the prefill logit is the whole
        generation), else None."""
        s = len(req.prompt)
        if self.paged:
            # page-sized compile buckets replace the power-of-two ones
            # (DESIGN.md §13): pad waste per prefill is < one page
            ps = self.kv_meta.page_size
            sb = min(-(-s // ps) * ps, self.window)
            sb = max(sb, s)           # window may not be a page multiple
        else:
            sb = _bucket(s, hi=self.window)
        with span(self.metrics, "engine.prefill", rid=req.rid, slot=slot,
                  tokens=s, bucket=sb):
            self.metrics["prefill_calls"] += 1
            self.metrics["prefill_tokens"] += s
            self.metrics["prefill_padded_tokens"] += sb
            if self.paged:
                self.kv_alloc.ensure_prefix(slot, min(s, self.window))
                fn = self._jit(("prefill_paged", sb), functools.partial(
                    self.model.paged_prefill_into_slot, window=self.window))
            else:
                fn = self._jit(("prefill", sb), self.model.prefill_into_slot)
            toks = np.zeros((1, sb), np.int32)
            pos = np.full((1, sb), -1, np.int32)
            toks[0, :s] = req.prompt
            pos[0, :s] = np.arange(s)
            # prefill_s: the jitted call and its block_until_ready
            with span(self.metrics, "engine.prefill.dispatch",
                      key="prefill_s"):
                if self.paged:
                    logits, self.kv_pool = fn(
                        self._serve_params, self.kv_pool,
                        jnp.asarray(self.kv_alloc.table[slot]),
                        jnp.asarray(toks), jnp.asarray(pos),
                        jnp.int32(s - 1))
                else:
                    logits, self.cache = fn(
                        self._serve_params, self.cache, jnp.asarray(toks),
                        jnp.asarray(pos), jnp.int32(slot), jnp.int32(s - 1))
            with span(self.metrics, "engine.prefill.wait", key="prefill_s"):
                jax.block_until_ready(logits)
            with span(self.metrics, "engine.sample", key="sample_s"):
                self._key, sub = jax.random.split(self._key)
                temp, top_k = self._sampling_of(req, temperature)
                tok = int(sample(logits, key=sub, temperature=temp,
                                 top_k=top_k,
                                 vocab_size=self.cfg.vocab_size)[0])
            now = time.perf_counter()
            req.out_tokens.append(tok)
            req.t_first = now
            self.metrics["tokens_generated"] += 1
            st = self.scheduler.slots[slot]
            st.last_token = tok
            if req.done():                      # max_new_tokens == 1
                self._retire(slot, now)
                return req.rid
        return None

    def _retire(self, slot: int, now: float) -> None:
        """Retire the request in ``slot`` and release its KV."""
        with span(self.metrics, "engine.retire",
                  rid=self.scheduler.slots[slot].req.rid):
            self.scheduler.retire(slot, now=now)
            self._release_slot_kv(slot)

    def _release_slot_kv(self, slot: int):
        """Retire a slot's KV: paged -> free its pages (tags invalidated
        on device before reuse); slot cache -> invalidate the row."""
        with span(self.metrics, "engine.kv_release", key="kv_release_s"):
            if self.paged:
                freed = self.kv_alloc.free_slot(slot)
                buf = np.zeros(self.kv_meta.chunks_per_slot, np.int32)
                buf[:len(freed)] = freed
                self.kv_pool = self._jit(
                    "paged_reset", self.model.paged_reset_pages)(
                        self.kv_pool, jnp.asarray(buf))
            else:
                self.cache = self._jit("reset_slot", self.model.reset_slot)(
                    self.cache, jnp.int32(slot))

    def _update_kv_metrics(self, active):
        """Per-iteration KV padding accounting (DESIGN.md §13), and the
        cache bytes the step attends (``kv_read_bytes``)."""
        tb = self._kv_token_bytes
        used = sum(min(st.position + 1, self.window)
                   for _, st in active) * tb
        if self.paged:
            alloc = self.kv_alloc.pages_in_use \
                * self.kv_meta.page_size * tb
        else:
            alloc = self.max_slots * self.window * tb
        self.metrics["kv_used_bytes"] = used
        self.metrics["kv_read_bytes"] += used
        self.metrics["kv_allocated_bytes"] = alloc
        self.metrics["kv_used_byte_iters"] += used
        self.metrics["kv_alloc_byte_iters"] += alloc

    def kv_waste_fraction(self) -> float:
        """Run-averaged fraction of allocated KV bytes never holding a
        valid token (bucket padding waste; ~0 under the paged cache)."""
        alloc = self.metrics["kv_alloc_byte_iters"]
        if alloc <= 0:
            return 0.0
        return 1.0 - self.metrics["kv_used_byte_iters"] / alloc

    # -- self-speculative decoding (DESIGN.md §17) ----------------------
    def set_speculation(self, k: int) -> None:
        """Set the draft depth for ladder-draft speculative decoding;
        ``0`` falls back to plain decode (the QoSController's low-
        acceptance auto-fallback calls this). Takes effect from the next
        iteration — no drain, no recompile (the plain step functions
        stay cached)."""
        self.speculate_k = max(0, int(k))

    def _draft_serve_params(self):
        """Serve-layout params with EVERY expert at the lowest ladder
        rung — the paper's all-quantized configuration, i.e. the free
        draft model (the low-rung banks already exist as quantized
        views of the same master weights; no new information, just the
        all-low layout). Cached across replans: the draft depends only
        on (ladder, group_size), never on the serving rung assignment
        or placement."""
        plan = self._plan_result.plan
        low = quantized_rungs(plan.ladder)[0]
        sig = (tuple(plan.ladder), plan.group_size, low)
        if self._draft_params is None or self._draft_sig != sig:
            draft_plan = dataclasses.replace(
                plan, bits=np.full_like(plan.bits, low),
                location=np.full_like(plan.location, DEVICE))
            self._draft_params = apply_precision_plan(
                self.params_train, self.cfg, draft_plan)
            self._draft_sig = sig
        return self._draft_params

    def _greedy_np(self, row: np.ndarray) -> int:
        """Host-side greedy pick, identical to ``sampler.sample``'s
        temperature<=0 branch (same -1e30 vocab-pad mask, same
        first-max tie-break) — the acceptance comparison must match
        what plain decode would emit, bit for bit."""
        v = self.cfg.vocab_size
        if v and row.shape[-1] > v:
            row = np.where(np.arange(row.shape[-1]) >= v, -1e30, row)
        return int(np.argmax(row))

    def _probs_np(self, row: np.ndarray, temp: float, top_k: int
                  ) -> np.ndarray:
        """Host-side mirror of ``sampler.sample_probs`` (f64): the
        categorical distribution the engine samples from at this
        temperature/top_k — both the draft proposal q and the verify
        target p for the rejection-sampled acceptance."""
        x = np.asarray(row, np.float64).copy()
        v = self.cfg.vocab_size
        if v and x.shape[-1] > v:
            x[v:] = -1e30
        x = x / temp
        if top_k:
            thresh = np.partition(x, -top_k)[-top_k]
            x = np.where(x < thresh, -1e30, x)
        x -= x.max()
        e = np.exp(x)
        return e / e.sum()

    def _spec_iteration(self, active, temperature: float,
                        retired: List[int]) -> List[int]:
        """One speculative iteration (DESIGN.md §17): K draft tokens per
        slot at the lowest ladder rung, ONE batched verify forward at
        the serving plan scoring all K+1 positions against the KV
        cache, longest-prefix acceptance (greedy) or chain rejection
        sampling (temperature>0), then device-side rollback of the
        rejected tail + paged-KV truncation.

        Per-slot draft depth is clamped to ``min(K, remaining-1,
        window-1-position)``: the remaining-token clamp keeps the
        emitted count inside the request's claim, the window clamp
        keeps all speculative writes in the UNWRAPPED ring region so a
        multi-token write can never clobber an entry a same-batch query
        still attends (a slot at the wrap boundary rides the verify as
        plain single-token decode). Overlap mode uses this sync step
        too — the per-layer lookahead pipeline stays plain-decode-only;
        expert streaming still runs through the (async) cache's
        synchronous interface."""
        K = self.speculate_k
        S = K + 1
        B = self.max_slots
        depth: Dict[int, int] = {}
        for i, st in active:
            rem = st.req.max_new_tokens - len(st.req.out_tokens)
            depth[i] = max(0, min(K, rem - 1,
                                  self.window - 1 - st.position))
        if self.paged:
            # map every chunk the draft+verify writes touch up front;
            # the admission claim already covers the full span
            for i, st in active:
                for j in range(depth[i] + 1):
                    self.kv_alloc.ensure_index(
                        i, (st.position + j) % self.window)
            step = self._jit("spec_paged", functools.partial(
                self.model.paged_spec_step_routed, window=self.window))
        else:
            step = self._jit("spec", self.model.spec_step_routed)

        def run_step(params, toks, pos):
            # one jit entry serves both shapes: draft (B,1), verify (B,S)
            if self.paged:
                logits, self.kv_pool, ids = step(
                    params, self.kv_pool,
                    jnp.asarray(self.kv_alloc.table),
                    jnp.asarray(toks), jnp.asarray(pos))
            else:
                logits, self.cache, ids = step(
                    params, self.cache, jnp.asarray(toks),
                    jnp.asarray(pos))
            return logits, ids

        self._key, k_draft, k_verify = jax.random.split(self._key, 3)
        u_draft = u_acc = u_res = None
        with span(self.metrics, "engine.decode", key="decode_s",
                  rows=len(active)):
            # -- draft pass: up to K single-token steps at the lowest rung
            draft_params = self._draft_serve_params()
            drafts: Dict[int, List[int]] = {i: [] for i, _ in active}
            q_rows: Dict[int, List[np.ndarray]] = {i: [] for i, _ in active}
            prev_tok = {i: st.last_token for i, st in active}
            for t in range(max(depth.values(), default=0)):
                toks = np.zeros((B, 1), np.int32)
                pos = np.full((B, 1), -1, np.int32)
                rows = [i for i, st in active if depth[i] > t]
                for i, st in active:
                    if depth[i] > t:
                        toks[i, 0] = prev_tok[i]
                        pos[i, 0] = st.position + t
                logits, _ = run_step(draft_params, toks, pos)
                lg = np.asarray(logits)[:, 0]
                for i in rows:
                    temp, top_k = self._sampling_of(
                        self.scheduler.slots[i].req, temperature)
                    if temp <= 0.0:
                        tok = self._greedy_np(lg[i])
                    else:
                        if u_draft is None:
                            u_draft = np.asarray(jax.random.uniform(
                                k_draft, (max(K, 1), B)))
                        q = self._probs_np(lg[i], temp, top_k)
                        cdf = np.cumsum(q)
                        tok = int(min(np.searchsorted(
                            cdf, float(u_draft[t, i]), side="right"),
                            len(cdf) - 1))
                        q_rows[i].append(q)
                    drafts[i].append(tok)
                    prev_tok[i] = tok
            # -- batched verify at the serving plan (exact) -------------
            toks = np.zeros((B, S), np.int32)
            pos = np.full((B, S), -1, np.int32)
            for i, st in active:
                toks[i, 0] = st.last_token
                pos[i, 0] = st.position
                for j, d in enumerate(drafts[i]):
                    toks[i, j + 1] = d
                    pos[i, j + 1] = st.position + j + 1
            logits, route_ids = run_step(self._serve_params, toks, pos)
            jax.block_until_ready(logits)
            lg = np.asarray(logits)                       # (B, S, V)
        # only the verify's routes feed the expert stream / histogram:
        # the draft banks are fully resident by construction
        rows = [i * S + j for i, _ in active
                for j in range(depth[i] + 1)]
        self._stream_experts(np.asarray(route_ids), rows)
        n_tok = sum(depth[i] + 1 for i, _ in active)
        e = self.cfg.moe.num_experts
        d = self.cfg.moe.top_k * n_tok
        uniq = e * (1.0 - (1.0 - 1.0 / e) ** d)
        self.metrics["transfer_s_est"] += \
            self._miss_bytes_per_tok * uniq / self.cfg.moe.top_k \
            / self.hw.host_link_bw
        # -- acceptance -------------------------------------------------
        keep = np.full((B,), np.iinfo(np.int32).max // 2, np.int32)
        emitted: Dict[int, List[int]] = {}
        for i, st in active:
            k_i = depth[i]
            temp, top_k = self._sampling_of(st.req, temperature)
            if temp <= 0.0:
                targets = [self._greedy_np(lg[i, j])
                           for j in range(k_i + 1)]
                a = 0
                while a < k_i and drafts[i][a] == targets[a]:
                    a += 1
                out = drafts[i][:a] + [targets[a]]
            else:
                if u_acc is None:
                    k_acc, k_res = jax.random.split(k_verify)
                    u_acc = np.asarray(jax.random.uniform(
                        k_acc, (B, max(K, 1))))
                    u_res = np.asarray(jax.random.uniform(
                        k_res, (B, S)))
                p = np.stack([self._probs_np(lg[i, j], temp, top_k)
                              for j in range(k_i + 1)])
                q = np.stack(q_rows[i]) if k_i \
                    else np.zeros((0, p.shape[1]))
                a, final = speculative_verify(
                    np.asarray(drafts[i][:k_i], np.int64), q, p,
                    u_acc[i, :k_i], u_res[i, :k_i + 1])
                out = drafts[i][:a] + [final]
            emitted[i] = out
            keep[i] = st.position + len(out) - 1   # last accepted pos
            self.metrics["spec_proposed"] += k_i
            self.metrics["spec_accepted"] += len(out) - 1
        # -- device-side rollback of the rejected tail ------------------
        if any(depth[i] for i, _ in active):
            if self.paged:
                self.kv_pool = self._jit(
                    "paged_rollback", self.model.paged_rollback)(
                        self.kv_pool, jnp.asarray(self.kv_alloc.table),
                        jnp.asarray(keep))
            else:
                self.cache = self._jit(
                    "rollback", self.model.rollback_slots)(
                        self.cache, jnp.asarray(keep))
        self._update_kv_metrics(active)
        self.metrics["iterations"] += 1
        self.metrics["decode_rows"] += len(active)
        if self.metrics["spec_proposed"]:
            self.metrics["acceptance_rate"] = \
                self.metrics["spec_accepted"] \
                / self.metrics["spec_proposed"]
        now = time.perf_counter()
        for i, st in active:
            for tok in emitted[i]:
                st.req.out_tokens.append(int(tok))
            self.metrics["tokens_generated"] += len(emitted[i])
            st.position += len(emitted[i])
            st.last_token = int(emitted[i][-1])
            if st.req.done():
                self._retire(i, now)
                retired.append(st.req.rid)
            elif self.paged and depth[i]:
                # free pages holding only rejected tokens (their tags
                # were invalidated by the rollback above); speculative
                # spans are pre-wrap by the depth clamp, so the live
                # ring is exactly the prefix 0..position-1
                self.kv_alloc.truncate(i, st.position)
        return retired

    def run_iteration(self, *, admit: bool = True,
                      temperature: float = 0.0) -> List[int]:
        """One scheduler iteration: join new requests into free slots,
        decode ONE token for every active slot, retire finished requests.
        Returns the rids retired this iteration."""
        if self._plan_result is None:
            raise RuntimeError(
                "no active plan: apply_target() or configure() first")
        with span(self.metrics, "engine.iteration"):
            return self._iteration(admit, temperature)

    def _iteration(self, admit: bool, temperature: float) -> List[int]:
        m = self.metrics
        retired: List[int] = []
        if admit:
            with span(m, "engine.admit"):
                joined = self.scheduler.admit()
            for slot, req in joined:
                rid = self._prefill_slot(slot, req, temperature)
                if rid is not None:
                    retired.append(rid)
        active = self.scheduler.active()
        if not active:
            return retired
        if self.speculate_k > 0:
            # ladder-draft speculation (DESIGN.md §17) replaces the
            # one-token body below; speculate_k == 0 keeps this method
            # byte-identical to the pre-speculation engine.
            return self._spec_iteration(active, temperature, retired)
        toks = np.zeros((self.max_slots, 1), np.int32)
        pos = np.full((self.max_slots,), -1, np.int32)  # idle rows masked
        for i, st in active:
            toks[i, 0] = st.last_token
            pos[i] = st.position
        if self.paged:
            # map the chunk each active slot's ring write lands in BEFORE
            # the jitted step (host-side page table, device-side pool)
            with span(m, "engine.kv_map"):
                for i, st in active:
                    self.kv_alloc.ensure_index(i, st.position % self.window)
        route_ids = None
        with span(m, "engine.decode", rows=len(active)):
            if self._pipeline:
                # overlap mode: decode through the per-layer lookahead
                # pipeline; expert streaming happens inside (DESIGN.md §12)
                logits = self._decode_pipelined(toks, pos,
                                                [i for i, _ in active])
            else:
                if self.paged:
                    decode = self._jit("decode_paged", functools.partial(
                        self.model.paged_decode_step_routed,
                        window=self.window))
                else:
                    decode = self._jit("decode", self.model.decode_step_routed)
                # decode_s: the jitted call and its block_until_ready
                with span(m, "engine.decode.dispatch", key="decode_s"):
                    if self.paged:
                        logits, self.kv_pool, route_ids = decode(
                            self._serve_params, self.kv_pool,
                            jnp.asarray(self.kv_alloc.table),
                            jnp.asarray(toks), jnp.asarray(pos))
                    else:
                        logits, self.cache, route_ids = decode(
                            self._serve_params, self.cache,
                            jnp.asarray(toks), jnp.asarray(pos))
                with span(m, "engine.decode.wait", key="decode_s"):
                    jax.block_until_ready(logits)
        self._update_kv_metrics(active)
        m["iterations"] += 1
        m["decode_rows"] += len(active)
        with span(m, "engine.sample", key="sample_s"):
            self._key, sub = jax.random.split(self._key)
            if any(st.req.sampling is not None for _, st in active):
                # heterogeneous per-request SamplingParams: sample
                # row-wise (the batched path below stays bit-identical
                # when no request carries its own parameters)
                new_toks = np.zeros((self.max_slots,), np.int32)
                keys = jax.random.split(sub, self.max_slots)
                for i, st in active:
                    temp, top_k = self._sampling_of(st.req, temperature)
                    new_toks[i] = int(sample(
                        logits[i:i + 1], key=keys[i], temperature=temp,
                        top_k=top_k, vocab_size=self.cfg.vocab_size)[0])
            else:
                new_toks = np.asarray(sample(
                    logits, key=sub, temperature=temperature,
                    vocab_size=self.cfg.vocab_size))
        with span(m, "engine.stream_experts"):
            if route_ids is not None:  # sync path (pipelined streams inline)
                self._stream_experts(np.asarray(route_ids),
                                     [i for i, _ in active])
            # analytical cross-check: expected UNIQUE streamed bytes of
            # this iteration under uniform routing. n_active rows draw
            # d = top_k * n_active experts per layer; each off-device
            # expert streams iff drawn at least once, so the per-token
            # expectation (miss_bytes_per_tok = sum_offdev size/E * top_k)
            # is rescaled by E * (1 - (1-1/E)^d) / top_k. Measured below
            # this estimate then isolates CROSS-iteration locality (the
            # LRU's contribution).
            e = self.cfg.moe.num_experts
            d = self.cfg.moe.top_k * len(active)
            uniq = e * (1.0 - (1.0 - 1.0 / e) ** d)
            m["transfer_s_est"] += \
                self._miss_bytes_per_tok * uniq / self.cfg.moe.top_k \
                / self.hw.host_link_bw
        now = time.perf_counter()
        for i, st in active:
            st.req.out_tokens.append(int(new_toks[i]))
            m["tokens_generated"] += 1
            st.position += 1
            st.last_token = int(new_toks[i])
            if st.req.done():
                self._retire(i, now)
                retired.append(st.req.rid)
        return retired

    def step(self, *, temperature: float = 0.0, seed: Optional[int] = None
             ) -> int:
        """Serve until the queue and all slots are empty; returns the
        number of requests finished by this call. (Compatibility wrapper —
        iteration-level control lives in ``run_iteration``.)"""
        if self._plan_result is None:
            raise RuntimeError(
                "no active plan: apply_target() or configure() first")
        if seed is not None:
            self._key = jax.random.key(seed)
        finished = 0
        while self.scheduler.has_work():
            finished += len(self.run_iteration(temperature=temperature))
        return finished

    # ------------------------------------------------------------------
    # Reporting
    # ------------------------------------------------------------------
    def throughput_tokens_per_s(self, include_transfer: bool = True
                                ) -> float:
        """Measured tokens/s. ``include_transfer`` charges the EXPOSED
        transfer time only (DESIGN.md §12) — for serial staging that IS
        the total blocked transfer time; in overlap mode the hidden
        portion already overlaps decode wall-clock and must not be
        double-counted."""
        t = self.metrics["decode_s"]
        if include_transfer:
            t += self.metrics["transfer_exposed_s"]
        return self.metrics["tokens_generated"] / max(t, 1e-9)

    def measured_overlap_efficiency(self) -> Optional[float]:
        """Measured overlap window as a fraction of decode compute —
        the runtime counterpart of ``HardwareModel.overlap_efficiency``
        (a LOWER bound when every transfer hid completely). None until
        any expert time was transferred, and None through a SHARED
        scoped cache view: its speculative traffic is accounted
        parent-globally, so the per-tenant hidden/total ratio is not
        measurable — folding the apparent ~0 into the hardware model
        would wrongly revert the frontier to the additive ranking."""
        if not self._owns_cache \
                and getattr(self.expert_cache, "parent", None) is not None:
            return None
        total = self.metrics["transfer_s"] + self.metrics["prefetch_s"]
        if total <= 0 or self.metrics["decode_s"] <= 0:
            return None
        eff = self.metrics["transfer_overlapped_s"] \
            / self.metrics["decode_s"]
        return max(0.0, min(1.0, eff))

    def calibrate_overlap(self) -> Optional[float]:
        """Fold the MEASURED overlap efficiency back into the analytic
        hardware model and invalidate the cached frontier (DESIGN.md
        §12), so subsequent plans/frontier walks rank configurations by
        the transfer time this deployment actually exposes. Returns the
        calibrated efficiency, or None when nothing was measured yet."""
        eff = self.measured_overlap_efficiency()
        if eff is None:
            return None
        self.hw = dataclasses.replace(self.hw, overlap_efficiency=eff)
        self.planner.recalibrate(self.hw)
        self._frontier = None
        return eff

    def close(self):
        """Release the transfer pipeline: join the async cache's worker
        threads (no-op for serial staging). A SHARED scoped view is only
        drained — its owner (e.g. the MultiTenantEngine) closes the
        space. Idempotent; the engine must not decode afterwards."""
        if self._owns_cache:
            self.expert_cache.close()
        else:
            self.expert_cache.drain()

    def latency_percentiles(self, qs=(50, 95),
                            last_n: Optional[int] = None
                            ) -> Dict[str, float]:
        return self.scheduler.latency_percentiles(qs, last_n=last_n)

    def reset_counters(self):
        """Zero the throughput counters (between benchmark operating
        points); plan/reconfig counters are preserved."""
        for k in ("tokens_generated", "decode_s", "prefill_s",
                  "transfer_s", "transfer_s_est",
                  "prefetch_s", "transfer_exposed_s",
                  "transfer_overlapped_s", "sample_s", "kv_release_s",
                  "prefill_calls", "prefill_tokens",
                  "prefill_padded_tokens", "decode_rows",
                  "expert_accesses", "expert_fetches", "iterations",
                  "kv_alloc_byte_iters", "kv_used_byte_iters",
                  "kv_read_bytes", "spec_proposed", "spec_accepted",
                  "acceptance_rate"):
            self.metrics[k] = 0 if isinstance(self.metrics[k], int) else 0.0
        self.expert_cache.stats.reset()

    def summary(self) -> str:
        p = self._plan_result
        lat = self.latency_percentiles()
        m = self.metrics
        overlap = ""
        if self._pipeline or m["prefetch_s"] or m["transfer_overlapped_s"]:
            overlap = (f" xfer[prefetch={m['prefetch_s']:.3f}s"
                       f" exposed={m['transfer_exposed_s']:.3f}s"
                       f" hidden={m['transfer_overlapped_s']:.3f}s]")
        rungs = [b for b in p.plan.ladder if b < 16]
        if len(rungs) <= 1:
            knobs = (f"E{rungs[0] if rungs else 4}="
                     f"{p.plan.num_q_experts}/{p.plan.quant.size}")
        else:
            # multi-rung ladder: num_q_experts conflates the rungs —
            # spell counts per rung like FrontierPoint.summary()
            knobs = "E[" + ",".join(
                f"{b}b={int((p.plan.bits == b).sum())}"
                for b in rungs) + f"]/{p.plan.bits.size}"
        # KV padding accounting (DESIGN.md §13): run-averaged allocated
        # vs used bytes; waste is the padding the paged cache eliminates
        it = max(m["iterations"], 1)
        kv = (f" kv[{'paged' if self.paged else 'slots'}"
              f" alloc={m['kv_alloc_byte_iters'] / it / 2**20:.2f}MiB"
              f" used={m['kv_used_byte_iters'] / it / 2**20:.2f}MiB"
              f" waste={self.kv_waste_fraction():.0%}]")
        spec = ""
        if m["spec_proposed"]:
            spec = (f" spec[k={self.speculate_k}"
                    f" acc={m['acceptance_rate']:.0%}"
                    f" {m['spec_accepted']}/{m['spec_proposed']}]")
        kv += spec
        return (f"plan[{p.preference} {knobs}"
                f" res={p.plan.resident_fraction():.0%}]"
                f" gen={m['tokens_generated']}tok"
                f" decode={m['decode_s']:.2f}s"
                f" +transfer={m['transfer_s']:.3f}s"
                f" (est {m['transfer_s_est']:.3f}s)"
                + overlap + kv +
                f" -> {self.throughput_tokens_per_s():.2f} tok/s"
                f" p50={lat['p50']*1e3:.0f}ms p95={lat['p95']*1e3:.0f}ms")
