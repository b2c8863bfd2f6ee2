"""Phase-level timing harness for the emulation stack.

Everything here is HOST-side instrumentation: jitted programs cannot be
timed from inside, so phases are measured by bracketing dispatches with
``jax.block_until_ready`` (async dispatch otherwise attributes a phase's
cost to whoever synchronizes first). Three tools:

``PhaseTimer``
    Accumulating named spans. ``with timer.span("synray") as mark:``
    times the body; register device values with ``mark(x)`` and the span
    blocks on them before reading the clock. ``summary()`` gives
    count/total/mean/best per phase.

``profile_phases``
    Times the AnnCore window phase-by-phase — the STP + synray current
    phase, the neuron integration, and the hoisted correlation window —
    by jitting each phase function separately (the same op trees the
    fused program runs; per-phase dispatch adds overhead, so the split
    is attribution, not an end-to-end time — ``total`` times the real
    fused ``run`` for that).

``profiler_trace`` / ``cache_snapshot`` / ``CacheDelta``
    ``jax.profiler`` trace hook (no-op when unavailable), and
    specializer-cache snapshots with eviction-storm detection: more
    misses than the LRU capacity within one delta means the working set
    thrashes the cache and every upload recompiles.
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


def profile_phases(core, state, row_spikes_t, row_addr_t,
                   iters: int = 5, timer: Optional[PhaseTimer] = None
                   ) -> Dict[str, Dict[str, float]]:
    """Per-phase timings of one AnnCore window on ``core``'s backend.

    Phases (the fused/blocked pipeline of ``AnnCore._run_windowed``):
      ``synray``  STP efficacy scan + whole-window synaptic currents
      ``neuron``  membrane integration (per-dt scan or time-blocked)
      ``corr``    hoisted correlation-sensor window
      ``total``   the actual fused ``core.run`` dispatch (ground truth —
                  the phase split re-dispatches per phase)
    """
    timer = timer or PhaseTimer()
    unroll = 4

    win = jax.jit(lambda s, ev, ad: core._window_currents(
        s, ev, ad, unroll)[:3])
    _, i_exc_t, i_inh_t = timer.time_fn(
        "synray", win, state, row_spikes_t, row_addr_t, iters=iters)

    neuron = jax.jit(lambda n, rc, ie, ii: core._neuron_window(
        n, rc, ie, ii, record_v=False, unroll=unroll))
    timer.time_fn("neuron", neuron, state.neuron, state.rate_counters,
                  i_exc_t, i_inh_t, iters=iters)

    from repro.core import correlation
    cfg = core.cfg
    corr = jax.jit(lambda c, ev, sp: correlation.window(
        c, ev, sp, tau_pre=cfg.neuron.tau_syn_exc,
        tau_post=cfg.neuron.tau_syn_exc, dt=cfg.dt,
        impl=core.kernel_impl))
    zero_sp = jax.numpy.zeros(
        (*row_spikes_t.shape[:-1], cfg.n_cols), jax.numpy.float32)
    timer.time_fn("corr", corr, state.corr, row_spikes_t, zero_sp,
                  iters=iters)

    total = jax.jit(core.run)
    timer.time_fn("total", total, state, row_spikes_t, row_addr_t,
                  iters=iters)
    return timer.summary()


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
