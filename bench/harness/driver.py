"""One run of one cell: set-up, the measured window, the readings, the
comparison with the reference, and the result line."""
from __future__ import annotations

import gc
import math
import os
import shutil
import statistics
import tempfile
import time
from typing import Callable, Dict, List, Optional

from bench.harness import check, s5, trace
from bench.harness.keys import run_key

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = os.path.join(BENCH, "layers.json")
# a traced window lasts at most this long: a trace of every op is large,
# and its reading counts against the run's time
TRACE_SECONDS = 8.0


def device_info(devices) -> Dict:
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return dict(platform=d.platform, kind=d.device_kind, count=len(devices),
                memory_peak_bytes=peak)


def run_cell(cell_spec: Dict, seed: int, seconds: float, traced: bool,
             metrics: List[Dict], reader: Callable, log: Callable,
             t_start: float, build_kw: Optional[Dict] = None,
             wrap: Optional[Callable] = None,
             device_planes: str = "/device:"):
    """Returns (result object, check lines). ``build_kw`` and ``wrap``
    let a test run the same path on the CPU with its program broken, and
    ``device_planes`` read the CPU's XLA ops in place of a device's."""
    import jax

    from repro.compile_cache import enable_compile_cache
    config, traffic = cell_spec["config"], cell_spec["traffic"]
    limits = cell_spec["limits"]
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = s5.CompileCounter()

    # -- set-up: build, state from the seed on the device, one warm-up call
    cell = s5.build(config, traffic, **(build_kw or {}))
    if wrap is not None:
        wrap(cell)
    kind = s5.kind_of(config)
    n_check = int(limits["check_trials"])
    sample = None
    if kind == "fleet":
        sample = check.sample_instances(seed, cell.prefix[0],
                                        int(limits["check_instances"]))
    later = []
    if cell.fresh:
        later = check.sample_calls(seed, int(limits["check_span"]),
                                   int(limits["check_experiments"]) - 1)
    state = cell.init(run_key(seed, 0))
    state, hist = cell.scanned(state, cell.stims)
    progs = {0: s5.check_slice(hist, n_check, sample)}
    jax.device_get((hist["reward"], hist["rates"]))
    del hist
    sites = {}
    if traced:
        for fn, args in ((cell.scanned, (state, cell.stims)),
                         (cell.init, (run_key(seed, 1),))):
            module, s = trace.hlo_sites(fn.lower(*args).compile().as_text())
            sites[module] = s
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s!r} s: {kind} cell, prefix {cell.prefix}, "
        f"{cell.trials} trials per call, calls {traffic['calls']}, "
        f"backend {cell.meta['core'].backend}")

    # -- the measured window, with the collector's pauses kept out of it
    gc.collect()
    gc.freeze()
    gc.disable()
    counter.armed = True
    logdir = None
    if traced:
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(logdir)
        with jax.profiler.TraceAnnotation("bench/window"):
            state, win, kept = s5.window(
                cell, state, seed, min(seconds, TRACE_SECONDS), 1, log,
                annotate=_annotate, keep=later, n_keep=n_check)
        jax.profiler.stop_trace()
    else:
        state, win, kept = s5.window(cell, state, seed, seconds, 1, log,
                                     keep=later, n_keep=n_check)
    progs.update({c: s5.check_slice(h, n_check, None)
                  for c, h in kept.items()})
    del kept
    counter.armed = False
    gc.enable()
    gc.unfreeze()
    dev = device_info(cell.devices)
    calls = win["call_s"]
    log(f"window {win['window_s']!r} s: {win['calls']} calls, "
        f"{win['trials']} trials, {win['chip_trials']} chip-trials; "
        f"compiles inside the window {counter.count}; call s median "
        f"{statistics.median(calls)!r} max {max(calls)!r}; readback s "
        f"median {statistics.median(win['readback_s'])!r}")
    med = statistics.median(calls)
    slow = [(i, c, r) for i, (c, r) in enumerate(zip(calls, win["readback_s"]))
            if c > 1.25 * med]
    if slow:
        log(f"slow calls (index, call s, of which readback s): {slow!r}")
    if counter.count:
        log("WARNING: something compiled inside the measured window")

    reduced = None
    if traced:
        events = trace.load_events(logdir, device_planes)
        shutil.rmtree(logdir, ignore_errors=True)
        table = trace.load_table(LAYERS)
        events["sites"] = sites
        reduced = trace.reduce(events, table)
        log(f"trace: device planes {sorted(events['lines'])}, lines "
            f"{next(iter(events['lines'].values()), [])}; busy "
            f"{reduced['busy_s']!r} s of {reduced['window_s']!r} s; layers "
            f"{ {k: round(v, 6) for k, v in reduced['layers_s'].items()} }")

    # -- free the program, then the reference
    ctx = dict(config=config, window=win, setup_s=setup_s, device=dev,
               trace=reduced, kind=kind, prefix=cell.prefix,
               chips_per_trial=cell.chips_per_trial)
    del state, cell
    gc.collect()
    t_ref = time.perf_counter()
    prog_net, ref = against_reference(config, traffic, seed, progs, sample,
                                      n_check)
    nums = check.numbers(prog_net, ref)
    correct, line = check.judge(nums, limits["limits"])
    cap = gate_capacities(config["experiment"]["trial_steps"],
                          config["chip"]["n_rows"] // 2)
    log(f"reference {time.perf_counter() - t_ref!r} s over "
        f"{ref['rates'].shape[1]} network(s) of experiments "
        f"{sorted(progs)}; spikes in the checked trials "
        f"{float(prog_net['rates'].sum())!r} (reference "
        f"{float(ref['rates'].sum())!r}); {check.diagnostics(prog_net, ref)}"
        f"; external event census per trial (events, per step) "
        f"{ref['census']} against the gate's capacities {cap}")

    out = {}
    for m in metrics:
        v = reader(m)(ctx)
        if v is not None and math.isfinite(v):
            out[m["name"]] = dict(value=float(v), unit=m["unit"])
    result = dict(correct=bool(correct), attempted=int(win["trials"]),
                  failed=0 if correct else int(win["trials"]),
                  metrics=out, device=dev)
    if reduced is not None:
        result["device"].update(busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
        result["breakdown"] = dict(device_ops=reduced["top_ops"],
                                   idle_gaps=reduced["idle_gaps"])
    result["check"] = {k: dict(value=nums[k], limit=limits["limits"][k])
                       for k in check.NUMBERS}
    return result, [f"check: correct {correct}", f"check: {line}"]


def against_reference(config: Dict, traffic: Dict, seed: int, progs: Dict,
                      sample, n_check: int):
    """The program's checked trials of each experiment ({call: history
    slice}) and the reference's, as [trial, network, ...] arrays with the
    experiments' networks side by side."""
    kind = s5.kind_of(config)
    prog = [{k: s5.to_network(v, kind) for k, v in progs[c].items()}
            for c in sorted(progs)]
    return _side_by_side(prog), reference_networks(
        config, traffic, seed, sorted(progs), sample, n_check)


def reference_networks(config: Dict, traffic: Dict, seed: int, calls,
                       sample, n_check: int, precision: str = "highest"):
    """The reference's checked trials of experiments ``calls``."""
    from bench.harness.reference import reference_run
    ref = [reference_run(config, traffic, seed, c, n_check, sample,
                         precision=precision) for c in calls]
    out = _side_by_side(ref)
    out["census"] = ref[0]["census"]
    return out


def _side_by_side(parts: List[Dict]) -> Dict:
    import numpy as np
    return {k: np.concatenate([p[k] for p in parts], 1) for k in s5.CHECKED}


def gate_capacities(T: int, R: int):
    """The density gate's capacities (events per window, per step) for a
    window of T steps over R rows of one Dale half, by the program's own
    rule, so that a run can say which route its traffic asks for."""
    from repro.core import events, synapse
    thr = synapse.SPARSE_THRESHOLD_CONST_ADDR
    return events.default_max_events(T, R, thr), events.default_k_cap(R, thr)


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation("bench/" + name)
