"""The engine's spans: one helper that puts a span on the profiler's clock
and, optionally, adds its host time to a metric.

``span(metrics, name, key=None, **args)`` enters a
``jax.profiler.TraceAnnotation(name, **args)``; with ``key`` it also adds
the body's ``perf_counter`` time to ``metrics[key]``. With no profiler
active the annotation records nothing, so the spans are always on: their
records live in the profiler's own trace. A ``TraceAnnotation`` with
keyword arguments writes its event as ``name#k=v,...#``; a reader matches
spans by the name up to ``#``.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator, Optional

import jax

__all__ = ["span"]


@contextlib.contextmanager
def span(metrics: Dict[str, Any], name: str, key: Optional[str] = None,
         **args) -> Iterator[None]:
    with jax.profiler.TraceAnnotation(name, **args):
        t0 = time.perf_counter()
        yield
        if key is not None:
            metrics[key] += time.perf_counter() - t0
