"""The program's own layer scopes in a profiler trace, and its counters.

The program wraps each layer in a ``jax.named_scope``
(``repro.obs.trace.LAYER_SCOPES``), which writes the scope path into the
``op_name`` metadata of every op traced under it. ``bench/scopes.json``
declares the scopes and their layers as data, so that a program without
scopes reads as all glue and nothing here raises.

  ``hlo_scopes``     each instruction of a compiled program -> the
                     innermost declared scope of its ``op_name``;
  ``reduce_scopes``  each scope's self time in a window, by the sweep of
                     ``trace.attribute``: an instant of device time goes
                     to the innermost op running then, and the op's time to
                     its innermost scope, so a parent's time leaves out its
                     declared children's;
  ``scope_layers``   the scopes' times summed into layers;
  ``host_events``    the host events of the thread that ran the window:
                     the benchmark's spans, JAX's dispatch of the
                     program's entry points, device-to-host transfers
                     (``trace.host_label`` names a device idle gap by
                     the innermost of them);
  ``counting_call``  set-up's first call again, built with the program's
                     telemetry counters on: the counters, and whether the
                     checked history is the same bit for bit.

``probe`` runs a traced window of a cell through all of these.
The benchmark's ``run_cell`` does not call them yet.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Tuple

from bench.harness import check, s5, trace
from bench.harness.keys import run_key

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPES = os.path.join(BENCH, "scopes.json")
LAYERS = os.path.join(BENCH, "layers.json")
UNSCOPED = "unscoped"
# JAX's host events for dispatching a jitted entry point and for reading a
# result back; the entry points are the ones a cell's window calls
ENTRY_POINTS = ("init", "scanned_training")
TRANSFERS = ("np.asarray(jax.Array)", "ArrayImpl.copy_to_host_async",
             "CommonPjRtBuffer::Await")

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _innermost_scope(op_name: str, declared) -> Optional[str]:
    """The last component of an ``op_name`` path that is a declared
    scope, or None."""
    for part in reversed(op_name.split("/")):
        if part in declared:
            return part
    return None


def hlo_scopes(hlo_text: str, declared
               ) -> Tuple[str, Dict[str, Optional[str]]]:
    """(module name, {instruction: innermost declared scope or None}) of a
    compiled program's text. An instruction without an ``op_name`` of its
    own that calls a computation (a fusion) takes the most common scope
    among the instructions of that computation that have one."""
    module = ""
    m = re.search(r"^HloModule\s+([^\s,]+)", hlo_text, re.M)
    if m:
        module = m.group(1)
    scopes, named = {}, set()
    members, calls = defaultdict(list), {}
    comp = None
    for line in hlo_text.splitlines():
        if line.strip() == "}":
            comp = None
            continue
        c = trace._COMP.match(line)
        if c and "=" not in line.split("(")[0]:
            comp = c.group(1)
            continue
        im = trace._INSTR.match(line)
        if not im or comp is None and not line.startswith(" "):
            continue
        name, rest = im.group(1), im.group(2)
        meta = trace._META.search(rest)
        op_name = _OP_NAME.search(meta.group(1)) if meta else None
        scopes[name] = None
        if op_name:
            named.add(name)
            scopes[name] = _innermost_scope(op_name.group(1), declared)
        if comp:
            members[comp].append(name)
        cl = trace._CALLS.search(rest)
        if cl:
            calls[name] = cl.group(1)
    for name, callee in calls.items():
        if name in named:
            continue
        votes = Counter(scopes[m] for m in members.get(callee, ())
                        if m in named)
        if votes:
            scopes[name] = votes.most_common(1)[0][0]
    return module, scopes


def reduce_scopes(events: Dict, op_scopes: Dict[str, Dict],
                  window: Optional[Tuple] = None) -> Dict:
    """Each declared scope's self time, and the unscoped rest
    (``UNSCOPED``), averaged over the devices (seconds): they add up to
    ``busy_s``. ``op_scopes`` is {module: {instruction: scope}} of the
    programs the window ran; ``window`` defaults to the host's
    ``bench/window`` span."""
    window = window or trace.host_window(events)
    per_dev = []
    for plane, ops in sorted(events["devices"].items()):
        if not ops:
            continue
        lo, hi = window if window else (
            min(o[2] for o in ops), max(o[2] + o[3] for o in ops))
        own, busy, _ = trace.attribute(ops, lo, hi)
        times = defaultdict(float)
        for i, ns in own.items():
            module, op = ops[i][0], ops[i][1]
            where = op_scopes.get(module)
            sc = where.get(op) if where is not None else next(
                (m[op] for m in op_scopes.values() if op in m), None)
            times[sc or UNSCOPED] += ns
        per_dev.append((times, busy))
    if not per_dev:
        raise ValueError("the trace holds no device op")
    n = len(per_dev)
    scopes = defaultdict(float)
    for times, _ in per_dev:
        for k, v in times.items():
            scopes[k] += v / n * 1e-9
    return dict(scopes_s=dict(scopes),
                busy_s=sum(b for _, b in per_dev) / n * 1e-9)


def scope_layers(scopes_s: Dict[str, float], table: Dict) -> Dict[str, float]:
    """The scopes' self times summed into their layers; the unscoped rest
    goes to the table's ``unscoped`` layer."""
    layers = defaultdict(float)
    for sc, secs in scopes_s.items():
        entry = table["scopes"].get(sc)
        layers[entry["layer"] if entry else table["unscoped"]] += secs
    return dict(layers)


def host_events(logdir: str) -> List:
    """[[name, start_ns, dur_ns], ...] of the host thread that ran the
    window (the line holding ``bench/window``): the benchmark's spans,
    the dispatch of the entry points, and device-to-host transfers."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    keep = tuple(f"PjitFunction({e})" for e in ENTRY_POINTS) + TRANSFERS
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = list(line.events)
            if not any(ev.name == "bench/window" for ev in evs):
                continue
            return [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in evs if ev.name.startswith(trace.HOST_PREFIX)
                    or ev.name in keep]
    return []


def counting_call(config: Dict, traffic: Dict, seed: int, first: Dict,
                  n_check: int, sample, build_kw: Optional[Dict] = None):
    """Set-up's first call (``init(run_key(seed, 0))`` and one scanned
    call) of the cell built with ``telemetry=True``. Returns (counters,
    whether its checked history equals ``first`` bit for bit, seconds)."""
    import numpy as np

    from repro.obs import trace as obs_trace
    t0 = time.perf_counter()
    cell = s5.build(config, traffic, telemetry=True, **(build_kw or {}))
    state = cell.init(run_key(seed, 0))
    state, hist = cell.scanned(state, cell.stims)
    got = s5.check_slice(hist, n_check, sample)
    counters = obs_trace.summary(state.tele)
    same = all(np.array_equal(first[k], got[k]) for k in s5.CHECKED)
    return counters, same, time.perf_counter() - t0


def gate_overflow_share(counters: Dict) -> float:
    """Gated windows that measured their census, found it did not fit the
    sparse route's capacities and ran dense, over gated windows; 0.0 where
    no window was gated."""
    gated = counters["gated_windows"]
    return counters["overflow_fallbacks"] / gated if gated else 0.0


def probe(cell_spec: Dict, seed: int, seconds: float, log,
          build_kw: Optional[Dict] = None,
          device_planes: str = "/device:") -> Dict:
    """A traced window of the cell, set up as ``run_cell`` sets it
    up, reduced both by ``bench/layers.json`` and by the program's scopes,
    then the counting call. Times in seconds of the window."""
    import jax

    config, traffic = cell_spec["config"], cell_spec["traffic"]
    limits = cell_spec["limits"]
    table = trace.load_table(SCOPES)
    declared = set(table["scopes"])
    cell = s5.build(config, traffic, **(build_kw or {}))
    n_check = int(limits["check_trials"])
    sample = None
    if s5.kind_of(config) == "fleet":
        sample = check.sample_instances(seed, cell.prefix[0],
                                        int(limits["check_instances"]))
    state = cell.init(run_key(seed, 0))
    state, hist = cell.scanned(state, cell.stims)
    first = s5.check_slice(hist, n_check, sample)
    del hist
    sites, op_scopes = {}, {}
    for fn, args in ((cell.scanned, (state, cell.stims)),
                     (cell.init, (run_key(seed, 1),))):
        text = fn.lower(*args).compile().as_text()
        module, sites[module] = trace.hlo_sites(text)
        op_scopes[module] = hlo_scopes(text, declared)[1]
    jax.block_until_ready(state)

    logdir = tempfile.mkdtemp(prefix="bench_scopes_")
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation("bench/window"):
        state, win, _ = s5.window(
            cell, state, seed, seconds, 1, log,
            annotate=lambda n: jax.profiler.TraceAnnotation("bench/" + n))
    jax.profiler.stop_trace()
    events = trace.load_events(logdir, device_planes)
    events["sites"] = sites
    host = host_events(logdir)
    shutil.rmtree(logdir, ignore_errors=True)
    by_table = trace.reduce(events, trace.load_table(LAYERS))
    by_scope = reduce_scopes(events, op_scopes)
    del state, cell

    counters, same, count_s = counting_call(config, traffic, seed, first,
                                            n_check, sample, build_kw)
    first_device = next(ops for _, ops in sorted(events["devices"].items())
                        if ops)
    gaps = [[trace.host_label(events, a, b),
             trace.host_label(dict(host=host), a, b),
             (b - a) * 1e-9] for a, b in trace.attribute(
                 first_device, *trace.host_window(events))[2]]
    return dict(window=win, busy_s=by_table["busy_s"],
                window_s=by_table["window_s"],
                layers_s=by_table["layers_s"],
                scopes_s=by_scope["scopes_s"],
                scope_layers_s=scope_layers(by_scope["scopes_s"], table),
                counters=counters, same_history=same, counting_s=count_s,
                gate_overflow_share=gate_overflow_share(counters),
                host_names=sorted({n for n, _, _ in host}),
                idle_gaps=sorted(gaps, key=lambda g: -g[2])[:10])
