"""From a profiler trace to per-layer device time.

``capture`` records a window with ``jax.profiler``; ``load_events`` keeps
what the reduction needs from the ``.xplane.pb``: each device's op
intervals with their HLO module and instruction, and the host spans the
benchmark wrote (``bench/...``). ``hlo_sites`` maps each instruction of a
compiled program to the source file and line its metadata names;
``layer_of`` maps a site to a layer by the data table ``bench/layers.json``;
``reduce`` attributes every instant of device time to the innermost op
running then, so the layers' times and the unattributed rest add up to
the busy time, the union of the op intervals.

The events format (a JSON object) is also what the committed excerpt of
a chip trace holds:
  {"devices": {plane: [[module, op, start_ns, dur_ns], ...]},
   "host": [[name, start_ns, dur_ns], ...],
   "sites": {module: {op: [opcode, [[file, function, line], ...]]}}}
"""
from __future__ import annotations

import glob
import heapq
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "bench/"
DEVICE_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"


def load_events(logdir: str, device_planes: str = "/device:") -> Dict:
    """Device op intervals and the benchmark's host spans of a trace.
    ``device_planes`` names the planes that hold the device's ops; a CPU
    rehearsal passes "/host:CPU", whose XLA ops carry the same stats.
    A TPU names each op event by its whole HLO instruction text and each
    program run on the "XLA Modules" line; an op's module is the run
    that contains it."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host, lines_seen = {}, [], {}
    for plane in data.planes:
        if plane.name.startswith(device_planes):
            ops, runs = [], []
            lines_seen[plane.name] = [ln.name for ln in plane.lines]
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    runs += [(float(ev.start_ns), float(ev.start_ns)
                              + float(ev.duration_ns), _name(ev.name))
                             for ev in line.events]
                if device_planes == "/device:" and \
                        line.name not in DEVICE_LINES:
                    continue
                for ev in line.events:
                    st = dict(ev.stats)
                    if "hlo_op" not in st and device_planes != "/device:":
                        continue
                    ops.append([str(st.get("hlo_module", "")),
                                _name(str(st.get("hlo_op", ev.name))),
                                float(ev.start_ns), float(ev.duration_ns)])
            _assign_modules(ops, runs)
            devices[plane.name] = ops
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return dict(devices=devices, host=host, lines=lines_seen)


def _name(text: str) -> str:
    """An instruction's or a module's name from a trace event's name
    ("fusion.12 = f32[..] fusion(..)", "jit_f(42)", "%copy.3")."""
    return re.split(r"[\s=(]", text.strip().lstrip("%"), 1)[0]


def _assign_modules(ops: List, runs: List) -> None:
    """Give each op without a module the program run that contains it."""
    runs.sort()
    j = 0
    for op in sorted((o for o in ops if not o[0]), key=lambda o: o[2]):
        while j < len(runs) and runs[j][1] < op[2]:
            j += 1
        if j < len(runs) and runs[j][0] <= op[2]:
            op[0] = runs[j][2]


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
# an instruction's own metadata, not a custom call's kernel_metadata={}
_META = re.compile(r"(?:^|[\s,])metadata=\{([^}]*)\}")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_TABLE_ROW = re.compile(r"^(\d+)\s+(.*)$")


def _frames_table(hlo_text: str) -> Dict[int, List]:
    """{stack frame id: [[file, function, line], ...] innermost first}
    from the FileNames / FunctionNames / FileLocations / StackFrames
    tables of a compiled program's text."""
    tables, section = defaultdict(dict), None
    for line in hlo_text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section = line
            continue
        if not line.strip() or line.startswith(("%", "ENTRY", "HloModule")):
            section = None if not line.strip() else section
            continue
        m = _TABLE_ROW.match(line) if section else None
        if m:
            tables[section][int(m.group(1))] = m.group(2)

    def field(text, name):
        f = re.search(name + r"=(\d+)", text)
        return int(f.group(1)) if f else 0
    files = {k: v.strip('"') for k, v in tables["FileNames"].items()}
    funcs = {k: v.strip('"') for k, v in tables["FunctionNames"].items()}
    locs = {k: (files.get(field(v, "file_name_id"), ""),
                funcs.get(field(v, "function_name_id"), ""),
                field(v, "line"))
            for k, v in tables["FileLocations"].items()}
    frames = {}
    for k, v in tables["StackFrames"].items():
        frames[k] = (field(v, "file_location_id"),
                     field(v, "parent_frame_id"))
    out = {}
    for k in frames:
        chain, seen, cur = [], set(), k
        while cur in frames and cur not in seen:
            seen.add(cur)
            loc, parent = frames[cur]
            if loc in locs:
                chain.append(list(locs[loc]))
            cur = parent
        out[k] = chain
    return out


def hlo_sites(hlo_text: str) -> Tuple[str, Dict[str, List]]:
    """(module name, {instruction: [opcode, frames]}) of a compiled
    program's text, where frames is the Python call stack its metadata
    names, innermost first, as [file, function, line]. A fusion without a
    stack of its own takes the most common one among the instructions it
    fuses."""
    module = ""
    m = re.search(r"^HloModule\s+([^\s,]+)", hlo_text, re.M)
    if m:
        module = m.group(1)
    stacks = _frames_table(hlo_text)
    sites, comp_members, calls = {}, defaultdict(list), {}
    comp = None
    for line in hlo_text.splitlines():
        if line.strip() == "}":
            comp = None
            continue
        c = _COMP.match(line)
        if c and "=" not in line.split("(")[0]:
            comp = c.group(1)
            continue
        im = _INSTR.match(line)
        if not im or comp is None and not line.startswith(" "):
            continue
        name, rest = im.group(1), im.group(2)
        op = _OPCODE.search(rest)
        frames = []
        meta = _META.search(rest)
        if meta:
            sf = re.search(r"stack_frame_id=(\d+)", meta.group(1))
            if sf:
                frames = stacks.get(int(sf.group(1)), [])
            else:
                f = re.search(r'source_file="([^"]*)"', meta.group(1))
                ln = re.search(r"source_line=(\d+)", meta.group(1))
                if f:
                    frames = [[f.group(1), "", int(ln.group(1)) if ln else 0]]
        sites[name] = [op.group(1) if op else "", frames]
        if comp:
            comp_members[comp].append(name)
        cl = _CALLS.search(rest)
        if cl:
            calls[name] = cl.group(1)
    for name, callee in calls.items():
        if sites[name][1]:
            continue
        votes = defaultdict(int)
        for member in comp_members.get(callee, ()):
            if sites[member][1]:
                votes[json.dumps(sites[member][1])] += 1
        if votes:
            sites[name][1] = json.loads(max(votes, key=votes.get))
    return module, sites


def layer_of(site: Optional[List], table: Dict) -> str:
    """The layer of an instruction: its collective opcode, else the first
    frame of its call stack, innermost first, that a rule of the table
    names by file or by function; anything else is the unattributed
    rest."""
    if not site:
        return table["unattributed"]
    opcode, frames = site
    for rule in table["layers"]:
        if opcode in rule.get("opcodes", ()):
            return rule["layer"]
    for file, func, _ in frames:
        names = set(func.split("."))
        for rule in table["layers"]:
            if any(f in file for f in rule.get("files", ())):
                return rule["layer"]
            for rel, funcs in rule.get("functions", {}).items():
                if file.endswith(rel) and names.intersection(funcs):
                    return rule["layer"]
    return table["unattributed"]


def _rel(path: str) -> str:
    """A source path from its ``repro/`` package on."""
    i = path.rfind("repro/")
    return path[i:] if i >= 0 else path


def host_window(events: Dict, name: str = "bench/window"
                ) -> Optional[Tuple[float, float]]:
    spans = [(s, s + d) for n, s, d in events["host"] if n == name]
    return spans[0] if spans else None


def attribute(ops: List, lo: float, hi: float):
    """Sweep the op intervals inside [lo, hi]: every instant of device
    time goes to the op that started last among those running then.
    Returns ({op index: ns}, busy ns, [(gap start, gap end), ...])."""
    iv = sorted((max(s, lo), min(s + d, hi), i)
                for i, (_, _, s, d) in enumerate(ops)
                if s + d > lo and s < hi and d > 0)
    bounds = sorted({x for a, b, _ in iv for x in (a, b)} | {lo, hi})
    own, busy, gaps = defaultdict(float), 0.0, []
    heap, j = [], 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(iv) and iv[j][0] <= a:
            heapq.heappush(heap, (-iv[j][0], iv[j][1], iv[j][2]))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        # ended entries below the top are dropped when they surface
        if heap:
            own[heap[0][2]] += b - a
            busy += b - a
        elif gaps and gaps[-1][1] == a:
            gaps[-1] = (gaps[-1][0], b)
        else:
            gaps.append((a, b))
    return own, busy, gaps


def reduce(events: Dict, table: Dict, window: Optional[Tuple] = None
           ) -> Dict:
    """Per-device busy time, per-layer time and idle gaps, averaged over
    the devices (seconds). ``window`` defaults to the host's
    ``bench/window`` span."""
    window = window or host_window(events)
    sites = events.get("sites", {})
    per_dev = []
    for plane, ops in sorted(events["devices"].items()):
        if not ops:
            continue
        lo, hi = window if window else (
            min(o[2] for o in ops), max(o[2] + o[3] for o in ops))
        own, busy, gaps = attribute(ops, lo, hi)
        layers, by_op = defaultdict(float), defaultdict(float)
        kernels = defaultdict(lambda: dict(seconds=0.0, launches=0))
        for i, ns in own.items():
            module, op = ops[i][0], ops[i][1]
            site = sites.get(module, {}).get(op) or next(
                (m[op] for m in sites.values() if op in m), None)
            layer = layer_of(site, table)
            layers[layer] += ns
            if site and site[0] == "custom-call" and site[1]:
                k = kernels[_rel(site[1][0][0])]
                k["seconds"] += ns * 1e-9
                k["launches"] += 1
            where = "?"
            if site and site[1]:
                f, fn, ln = site[1][0]
                where = f"{os.path.basename(f)}:{ln} {fn}"
            by_op[f"{op} [{layer}] {where}"] += ns
        per_dev.append(dict(busy=busy, window=hi - lo, layers=layers,
                            by_op=by_op, gaps=gaps, kernels=kernels))
    if not per_dev:
        raise ValueError("the trace holds no device op")
    n = len(per_dev)
    layers = defaultdict(float)
    by_op = defaultdict(float)
    for d in per_dev:
        for k, v in d["layers"].items():
            layers[k] += v / n
        for k, v in d["by_op"].items():
            by_op[k] += v / n
    kernels = defaultdict(lambda: dict(seconds=0.0, launches=0.0))
    for d in per_dev:
        for k, v in d["kernels"].items():
            kernels[k]["seconds"] += v["seconds"] / n
            kernels[k]["launches"] += v["launches"] / n
    gaps = sorted(per_dev[0]["gaps"], key=lambda g: g[0] - g[1])[:10]
    return dict(
        busy_s=sum(d["busy"] for d in per_dev) / n * 1e-9,
        window_s=sum(d["window"] for d in per_dev) / n * 1e-9,
        layers_s={k: v * 1e-9 for k, v in layers.items()},
        kernels=dict(kernels),
        top_ops=[[k, v * 1e-9] for k, v in
                 sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        idle_gaps=[[host_label(events, a, b), (b - a) * 1e-9]
                   for a, b in gaps],
        devices=n)


def host_label(events: Dict, a: float, b: float) -> str:
    """What the host was doing at the middle of a device idle gap: the
    innermost benchmark span open then."""
    mid = 0.5 * (a + b)
    best = None
    for name, s, d in events["host"]:
        if s <= mid <= s + d and name != "bench/window" and (
                best is None or s > best[1]):
            best = (name, s)
    return best[0] if best else "host: outside the benchmark's spans"


def load_table(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)
