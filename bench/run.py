"""The chip benchmark of the BrainScaleS-2 emulation.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the accelerators of this machine:
builds the Section 5 closed loop of the cell's configuration through the
program's normal path, warms up (set-up, ``setup_s``), drives it for
``--seconds`` with the cell's traffic, and checks what the timed program
produced against the plain reference. ``--trace 1`` takes the per-layer
metrics from a profiler trace of the window instead of the end-to-end
ones.

Everything a cell needs is found by name: its configuration file, its
traffic file ``bench/traffic/<traffic>.json``, its limits
``bench/limits/<cell>.json``, and a reader ``bench/metrics/<metric>.py``
for each metric that has one. A per-layer metric named ``<x>.fleet`` or
``<x>.loop`` with no reader of its own is its layer's device time per
emulated chip-trial or per trial (``readers.layer_us``), the layer being
the one its entry names. Info goes to standard error; the last line of standard
output is one JSON object. A machine without a TPU, or with fewer chips
than the cell asks for, gets exit code 2 and no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[0:0] = [ROOT, os.path.join(ROOT, "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_spec(workload: str) -> dict:
    """The cell's entries and files, found by name."""
    spec = load_json(ROOT, "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    return dict(spec=spec, workload=wl,
                config=load_json(ROOT, cfg_entry["file"]),
                traffic=load_json(BENCH, "traffic", wl["traffic"] + ".json"),
                limits=load_json(BENCH, "limits", workload + ".json"))


def metrics_for(spec: dict, workload: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    ones: those that list the cell, or that list no cells and move an
    end-to-end metric the cell reports."""
    def listed(m):
        return workload in m["workloads"] if "workloads" in m else None
    e2e = [m for m in spec["end_to_end"] if listed(m) in (True, None)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if listed(m) or (listed(m) is None and m["moves"] in names)]


# the default reader's unit of work, by the metric name's last part
PER = {"fleet": "chip_trial", "loop": "trial"}


def reader(metric: dict):
    """The metric's reader: ``bench/metrics/<name>.py`` where it exists,
    else its layer's device time per unit of work."""
    name = metric["name"]
    path = os.path.join(BENCH, "metrics", name + ".py")
    if not os.path.exists(path):
        from bench.harness.readers import layer_us
        layer, per = metric["layer"], PER[name.rsplit(".", 1)[-1]]
        return lambda run: layer_us(run, layer, per)
    sp = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell_spec = load_spec(args.workload)

    import jax
    devs = jax.devices()
    wl = cell_spec["workload"]
    if devs[0].platform != "tpu" or len(devs) < int(wl["chips"]):
        log(f"FAIL: cell {wl['name']} needs {wl['chips']} TPU chip(s); JAX "
            f"found {len(devs)} {devs[0].platform} device(s)")
        return 2
    from bench.harness import driver
    result, check_lines = driver.run_cell(
        cell_spec, args.seed, args.seconds, bool(args.trace),
        metrics_for(cell_spec["spec"], wl["name"], bool(args.trace)),
        reader, log, T_START)
    for line in check_lines:
        log(line)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
