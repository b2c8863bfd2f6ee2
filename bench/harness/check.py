"""The comparison that decides ``correct``.

Set-up drives the compiled program from ``init`` through its first call,
the very object the window then runs on. The first ``check_trials``
trials of that call are compared with the reference (``reference.py``),
computed after the window has closed: for ``check_instances`` fleet
instances drawn from the seed, or for the whole network of a single chip
or a wafer. Where every call is a fresh experiment (``calls: fresh``),
``check_experiments - 1`` more experiments of the window, drawn from the
seed among its first ``check_span`` calls, are checked the same way.
The number compared is ``networks_off``: how many of the checked
networks (fleet instances or experiments) depart from the reference
anywhere in the checked trials, in a spike count, an eligibility code,
or a signed weight beyond ``W_ROUNDING``.

A float32 program and its reference sum in other orders, and once in a
while that moves one spike by a step in one network (its eligibility
codes then differ in that neuron's column, and now and then a count by
one); the limit leaves room for a few such networks, while a lower
precision, a frozen state, a dropped exchange or altered answers move
most of them. The widest spike-count gap is printed beside it: sound runs
read up to 1 and every fault 1 or 2, so it separates nothing. The limit
is kept per cell in ``bench/limits/<cell>.json`` with the readings it was
set from.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

NUMBERS = ("networks_off",)
# float32 rounding of the signed weights between program and reference
# (sums of the rule's terms in another order read 2e-6 to 6e-6)
W_ROUNDING = 1e-3


def sample_instances(seed: int, fleet: int, k: int) -> np.ndarray:
    """``k`` distinct fleet instances drawn from the seed, always with the
    first and the last instance among them."""
    rng = np.random.default_rng(int(seed) % 2 ** 64)
    k = min(k, fleet)
    pick = set(rng.choice(fleet, size=k, replace=False).tolist())
    pick |= {0, fleet - 1}
    while len(pick) > k:
        pick.remove(max(pick - {0, fleet - 1}))
    return np.asarray(sorted(pick), np.int64)


def sample_calls(seed: int, span: int, k: int) -> List[int]:
    """``k`` distinct window calls among 1 .. ``span``, drawn from the
    seed (call 0 is set-up's)."""
    rng = np.random.default_rng((int(seed) + 1) % 2 ** 64)
    return sorted(int(c) + 1 for c in rng.choice(span, size=min(k, span),
                                                  replace=False))


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers of [trial, network, ...] arrays of the
    program and the reference."""
    for k in ("rates", "elig", "w"):
        if prog[k].shape != ref[k].shape:
            raise ValueError(f"{k}: program {prog[k].shape} vs reference "
                             f"{ref[k].shape}")

    def gap(k):
        d = np.abs(np.asarray(prog[k], np.float64)
                   - np.asarray(ref[k], np.float64))
        return np.where(np.isfinite(d), d, np.inf)
    S = prog["rates"].shape[1]
    off = np.zeros(S, bool)
    for k, tol in (("rates", 0.0), ("elig", 0.0), ("w", W_ROUNDING)):
        off |= (gap(k) > tol).reshape(gap(k).shape[0], S, -1).any(
            axis=(0, 2))
    return dict(networks_off=float(off.sum()))


def diagnostics(prog: Dict, ref: Dict) -> Dict:
    """Counts beside the numbers: how many spike counts and eligibility
    entries differ per checked trial, and the widest gaps."""
    out = {}
    for k in ("rates", "elig", "w"):
        d = np.abs(np.asarray(prog[k], np.float64)
                   - np.asarray(ref[k], np.float64))
        out[f"{k}_widest"] = float(d.max())
        out[f"{k}_differ_per_trial"] = (d > 0).reshape(
            d.shape[0], -1).sum(1).tolist()
    return out


def judge(nums: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, str]:
    """(correct, "name number limit ..." line)."""
    ok = all(math.isfinite(nums[k]) and nums[k] <= limits[k]
             for k in NUMBERS)
    line = " ".join(f"{k} {nums[k]!r} limit {limits[k]!r}" for k in NUMBERS)
    return ok, line

