"""Phase-level timing harness for the emulation stack.

Everything here is HOST-side instrumentation: jitted programs cannot be
timed from inside, so phases are measured by bracketing dispatches with
``jax.block_until_ready`` (async dispatch otherwise attributes a phase's
cost to whoever synchronizes first). Two tools:

``PhaseTimer``
    Accumulating named spans. ``with timer.span("synray") as mark:``
    times the body; register device values with ``mark(x)`` and the span
    blocks on them before reading the clock. ``summary()`` gives
    count/total/mean/best per phase.

``profiler_trace`` / ``cache_snapshot`` / ``CacheDelta``
    ``jax.profiler`` trace hook (``None`` is a no-op; the program's layer
    scopes, ``repro.obs.trace.LAYER_SCOPES``, name each device op in the
    trace), and specializer-cache snapshots with eviction-storm
    detection: more misses than the LRU capacity within one delta means
    the working set thrashes the cache and every upload recompiles.
"""
from __future__ import annotations

import time
import warnings
from contextlib import contextmanager
from typing import Dict, List, Optional

import jax


class PhaseTimer:
    """Accumulating ``block_until_ready``-bracketed named spans."""

    def __init__(self):
        self.samples: Dict[str, List[float]] = {}

    @contextmanager
    def span(self, name: str):
        marks = []
        t0 = time.perf_counter()
        yield marks.append
        if marks:
            jax.block_until_ready(marks)
        self.samples.setdefault(name, []).append(time.perf_counter() - t0)

    def time_fn(self, name: str, fn, *args, iters: int = 1, warmup: int = 1,
                **kw):
        """Time ``fn(*args, **kw)`` ``iters`` times (after ``warmup``
        unrecorded calls — compile + cache fill), recording one span per
        iteration. Returns the last result."""
        out = None
        for _ in range(warmup):
            out = fn(*args, **kw)
            jax.block_until_ready(out)
        for _ in range(iters):
            with self.span(name) as mark:
                out = fn(*args, **kw)
                mark(out)
        return out

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-phase {count, total_us, mean_us, best_us}."""
        out = {}
        for name, ts in self.samples.items():
            out[name] = dict(count=len(ts), total_us=sum(ts) * 1e6,
                             mean_us=sum(ts) / len(ts) * 1e6,
                             best_us=min(ts) * 1e6)
        return out


@contextmanager
def profiler_trace(logdir: Optional[str]):
    """``jax.profiler.trace`` hook: collect a device trace into ``logdir``
    (viewable in TensorBoard / Perfetto). ``None`` makes this a no-op, so
    callers can thread a knob through unconditionally; a requested trace
    that the profiler cannot start raises."""
    if logdir is None:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# Specializer-cache observability
# ---------------------------------------------------------------------------

def cache_snapshot() -> dict:
    """Current ``repro.ppuvm.specialize`` cache stats
    (hits/misses/evictions/size/max_size)."""
    from repro.ppuvm import specialize
    return specialize.cache_stats()


def eviction_storm(delta: dict) -> bool:
    """True when a stats *delta* shows more misses than the LRU capacity:
    the program working set cannot fit, every upload re-specializes, and
    the cache degrades to pure overhead. Raise the cap or deduplicate the
    program stream."""
    return delta.get("misses", 0) > delta.get("max_size", 0) > 0


class CacheDelta:
    """Context manager capturing the specializer-cache stats delta over a
    run; warns on an eviction storm.

        with CacheDelta() as cd: ...
        cd.delta  # {"hits": ..., "misses": ..., "evictions": ...}
    """

    def __init__(self, warn: bool = True):
        self.warn = warn
        self.delta: dict = {}

    def __enter__(self):
        self._before = cache_snapshot()
        return self

    def __exit__(self, *exc):
        after = cache_snapshot()
        self.delta = {k: after[k] - self._before[k]
                      for k in ("hits", "misses", "evictions")}
        self.delta["size"] = after["size"]
        self.delta["max_size"] = after["max_size"]
        if self.warn and eviction_storm(self.delta):
            warnings.warn(
                f"specializer-cache eviction storm: {self.delta['misses']} "
                f"misses / {self.delta['evictions']} evictions exceed the "
                f"LRU capacity ({after['max_size']}) within one run — the "
                "program working set thrashes the cache",
                RuntimeWarning, stacklevel=2)
        return False
