"""Public serving surface — declarative QoS API (DESIGN.md §9).

Callers declare *targets*, not knob values: a deployment states a
:class:`~repro.core.pareto.QoSTarget` (min tokens/s, max quality loss,
memory budget), each request states a :class:`RequestSLO` (priority,
optional deadline) and :class:`SamplingParams`; the engine picks the MoP
configuration off its :class:`~repro.core.pareto.ParetoFrontier` and the
:class:`~repro.serving.qos.QoSController` keeps it on target at runtime.

    from repro.serving.api import (EngineConfig, QoSTarget, ServeRequest,
                                   RequestSLO, build_engine)
    engine = build_engine(cfg, params, EngineConfig(max_slots=8))
    engine.apply_target(QoSTarget(min_tokens_per_s=8.0,
                                  mem_budget_bytes=40 * 2**30))
    rid = engine.submit_request(ServeRequest(prompt,
                                             slo=RequestSLO(priority=1)))
    engine.step()
    print(engine.result(rid))

The imperative ``engine.configure(mem_budget_bytes, preference, num_q)``
survives as a deprecated shim that builds a ``QoSTarget`` internally.

Importing this module does not build any jax computation (the model
stack loads only when ``build_engine`` constructs an engine), though jax
itself is transitively imported via the cost model's config types.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.cost_model import HardwareModel
from repro.core.pareto import (  # noqa: F401  (public re-exports)
    FrontierPoint, InfeasibleTarget, ParetoFrontier, QoSTarget,
)
from repro.serving.multi import (  # noqa: F401  (public re-exports)
    MultiTenantEngine, ReplanReport, ResourceArbiter, TenantSpec,
)
from repro.serving.scheduler import (  # noqa: F401  (public re-exports)
    Request, RequestSLO, SamplingParams,
)

__all__ = [
    "EngineConfig", "SamplingParams", "RequestSLO", "ServeRequest",
    "ServeResult", "QoSTarget", "FrontierPoint", "ParetoFrontier",
    "InfeasibleTarget", "build_engine",
    "MultiTenantEngine", "TenantSpec", "ResourceArbiter", "ReplanReport",
]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Typed construction parameters for the serving engine — replaces the
    kwarg soup of ``AdaptiveServingEngine.__init__`` (DESIGN.md §9).

    Capacity:
      * ``max_slots`` — decode batch width (rows of the slot KV cache);
      * ``max_len``   — per-slot KV window (prompt + max_new_tokens cap);
      * ``max_active_tokens`` / ``max_queue`` — admission-control knobs
        (see ``serving/scheduler.py``).
    Expert streaming:
      * ``swap_bytes`` — device LRU swap capacity for non-resident
        experts; ``prefetch`` enables the speculative prefetch cache.
      * ``overlap`` — async overlapped expert streaming (DESIGN.md §12):
        transfers run on an ``AsyncExpertCache`` worker pool and the
        engine decodes through the per-layer lookahead pipeline, hiding
        transfer time under layer compute. ``overlap_efficiency`` seeds
        the analytic overlap window (fraction of t_compute; ``None`` =
        0.85 when overlap is on, 0.0 otherwise); the engine refines it
        from measurement via ``calibrate_overlap()``. Off = the paper's
        serial staging, bit-identical to the historical path.
    Precision:
      * ``ladder`` — the deployment's precision ladder (descending rung
        tuple, e.g. ``(16, 8, 4)``; DESIGN.md §11). ``None`` keeps the
        model config's ladder (binary ``(16, bits)`` by default, which
        reproduces the pre-ladder plans bit-identically).
    KV cache (DESIGN.md §13):
      * ``paged_kv`` — serve through the paged KV cache: fixed-size pages
        + a per-slot page table instead of fully-windowed slot rows.
        Decode is bit-identical to the slot cache (tested); allocated KV
        bytes track actual tokens per page instead of slots x window.
        ``False`` keeps the slot cache as the A/B baseline.
      * ``page_size`` — tokens per KV page.
      * ``kv_pool_pages`` — physical pool size (incl. the null page);
        ``None`` = worst case (slots x window). A smaller pool reclaims
        HBM; the engine derives an admission cap from it so allocation
        never dead-ends mid-flight.
      * ``kv_reserve`` — credit the HBM a sub-worst-case pool reclaims
        (vs the bucketed slot cache) to ``QoSTarget.mem_budget_bytes``
        when resolving targets on the frontier, feeding the savings back
        into the expert-residency axis.
    Hardware:
      * ``hw`` — analytic hardware model; None measures the host link
        bandwidth once per process and takes the device's published
        peaks (``cost_model.hardware_for_device``).
      * ``use_kernel`` — expert FFN through the Pallas kernels (one per
        rung). ``None`` (default) = on a TPU, and the jnp dequant
        reference on the CPU test backend; any other backend raises.
        ``False`` forces the reference, the oracle the kernels are
        checked against.
    Speculative decode (DESIGN.md §17):
      * ``speculate`` — draft depth K for ladder-draft self-speculative
        decoding: each iteration drafts K tokens per slot with every
        expert forced to the LOWEST ladder rung (the banks are already
        resident — zero extra weight bytes), then one batched verify
        forward at the serving plan scores all K+1 positions and accepts
        the longest matching prefix. Greedy output is token-identical to
        plain decode (tested); temperature>0 uses rejection sampling via
        ``serving/sampler.py``. ``0`` (default) is plain decode,
        byte-identical to the pre-speculation engine.
    Expert parallelism (DESIGN.md §16):
      * ``ep`` — EP shard count of the mesh the engine decodes over.
        The planner/frontier then round per-rung counts to multiples of
        ``ep`` (rung banks must split evenly over the mesh) and add the
        PEER placement tier (experts in a peer device's HBM, reached
        via the all2all at interconnect bandwidth). ``1`` (default) is
        the single-device engine bit-for-bit; the mesh itself is passed
        to ``build_engine(mesh=...)`` (see ``serving/ep``).
    """
    max_slots: int = 8
    max_len: int = 256
    use_kernel: Optional[bool] = None
    max_active_tokens: Optional[int] = None
    max_queue: Optional[int] = None
    swap_bytes: Optional[int] = None
    prefetch: bool = False
    overlap: bool = False
    overlap_efficiency: Optional[float] = None
    ladder: Optional[Tuple[int, ...]] = None
    hw: Optional[HardwareModel] = None
    paged_kv: bool = True
    page_size: int = 16
    kv_pool_pages: Optional[int] = None
    kv_reserve: bool = False
    ep: int = 1
    speculate: int = 0


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    """One generation request on the declarative surface."""
    prompt: np.ndarray
    max_new_tokens: int = 16
    sampling: Optional[SamplingParams] = None
    slo: RequestSLO = dataclasses.field(default_factory=RequestSLO)


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """Completed request: tokens + the QoS the request actually got."""
    rid: int
    tokens: List[int]
    latency_s: float
    ttft_s: Optional[float]
    priority: int
    deadline_s: Optional[float]
    deadline_met: Optional[bool]   # None when no deadline was declared

    @classmethod
    def from_request(cls, req: Request) -> "ServeResult":
        if req.t_done is None:
            raise ValueError(f"request {req.rid} is still in flight")
        return cls(rid=req.rid, tokens=list(req.out_tokens),
                   latency_s=req.latency_s, ttft_s=req.ttft_s,
                   priority=req.slo.priority,
                   deadline_s=req.slo.deadline_s,
                   deadline_met=req.deadline_met)

    def summary(self) -> str:
        dl = ("" if self.deadline_met is None else
              f" deadline={'MET' if self.deadline_met else 'MISSED'}")
        return (f"req {self.rid} prio={self.priority}: "
                f"{len(self.tokens)} tok in {self.latency_s * 1e3:.0f} ms"
                + dl)


def results_of(requests: Sequence[Request]) -> List[ServeResult]:
    """Batch conversion helper for completed scheduler requests."""
    return [ServeResult.from_request(r) for r in requests]


def build_engine(cfg, params, config: Optional[EngineConfig] = None, *,
                 mesh=None, expert_cache=None):
    """Construct an :class:`~repro.serving.engine.AdaptiveServingEngine`
    from an :class:`EngineConfig` (lazy import keeps this module jax-free
    until an engine is actually built). ``expert_cache`` attaches a
    tenant-scoped view of a shared swap space
    (:meth:`~repro.core.expert_cache.ExpertCache.scoped`) for
    multi-tenant deployments (DESIGN.md §10)."""
    from repro.serving.engine import AdaptiveServingEngine
    return AdaptiveServingEngine(cfg, params, mesh=mesh,
                                 config=config or EngineConfig(),
                                 expert_cache=expert_cache)
