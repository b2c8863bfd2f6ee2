"""Shared arithmetic of the metric readers in ``bench/metrics/``.

A reader is ``read(run) -> float | None``; ``run`` holds the window's
host-clock stats (``window``), ``setup_s``, the device (``device``), the
trace reduction (``trace``, None without ``--trace 1``) and the cell's
shape (``kind``, ``prefix``, ``chips_per_trial``, ``config``). A reader
that finds nothing to read returns None and its metric is left out.
"""
from __future__ import annotations

from typing import Optional

from bench.harness import roofline

def work(run, per: str) -> float:
    """Trials (``per="trial"``) or emulated chip-trials of the window."""
    return run["window"]["chip_trials" if per == "chip_trial" else "trials"]


def layer_us(run, layer: str, per: str) -> Optional[float]:
    """Device microseconds of a layer per trial or per chip-trial, from
    the trace of the window; None when the layer ran nothing."""
    tr = run["trace"]
    if tr is None or tr["layers_s"].get(layer, 0.0) <= 0.0:
        return None
    return tr["layers_s"][layer] / work(run, per) * 1e6


def idle_share(run) -> Optional[float]:
    tr = run["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]


def synray_roofline(run) -> Optional[float]:
    """The dense ``synray`` kernel's share of its roofline (%): the least
    time its launches in the window could take on this chip over the
    device time they took. None where the kernel did not run."""
    tr = run["trace"]
    k = tr and tr["kernels"].get("repro/kernels/synray/kernel.py")
    if not k or k["launches"] <= 0 or k["seconds"] <= 0:
        return None
    ch, ex = run["config"]["chip"], run["config"]["experiment"]
    instances = run["prefix"][0] if run["prefix"] else 1
    if run["kind"] == "wafer":
        instances = 1          # each device runs its own chip's launch
    ops, nbytes = roofline.synray_cost(instances, ex["trial_steps"],
                                       ch["n_rows"] // 2,
                                       ch["n_cols"] // run["chips_per_trial"]
                                       if run["kind"] == "wafer"
                                       else ch["n_cols"])
    share, _ = roofline.roofline_share(ops * k["launches"],
                                       nbytes * k["launches"], k["seconds"],
                                       run["device"]["kind"])
    return share
