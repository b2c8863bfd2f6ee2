"""Reproduction test for the paper's §5 R-STDP experiment (Fig. 11).

Claim: "The mean expected reward converges to approximately one for all
neurons during training ... despite pattern overlap" (40% overlap).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hybrid import (RSTDPConfig, make_experiment,
                               make_scanned_training, run_training)


def _trailing(mr, sel, n=150):
    """Mean over the last n trials of the population-median <R> (the xi
    random walk keeps exploring, so instantaneous <R> dips transiently —
    the paper's Fig. 11 likewise shows 15/85% error bands)."""
    med = np.median(mr[-n:, sel], axis=1)
    return float(np.mean(med))


def test_fig11_reward_converges_to_one():
    out, state, meta = run_training(n_trials=450, seed=0)
    even = np.asarray(meta["even"]) > 0
    mr = out["mean_reward"]
    te = _trailing(mr, even)
    to = _trailing(mr, ~even)
    assert te > 0.85, f"even population trailing <R> = {te}"
    assert to > 0.85, f"odd population trailing <R> = {to}"
    # discrimination: weights from pattern-A channels are excitatory toward
    # the A-population and depressed toward the B-population
    w = out["w_signed_final"]
    ma = np.asarray(meta["mask_a"]) > 0
    assert w[ma][:, even].mean() > 5.0
    assert w[ma][:, even].mean() > w[ma][:, ~even].mean() + 10.0


def test_reward_improves_from_start():
    """Cheap smoke: trailing reward clearly above the silent-attractor
    baseline (2/3) after 250 trials."""
    out, state, meta = run_training(n_trials=250, seed=1)
    mr = out["mean_reward"]
    assert _trailing(mr, slice(None), n=80) > 0.75


def test_overlap_zero_also_converges():
    """Zero overlap is NOT an easier/faster instance: disjoint patterns use
    2*pattern_size = 10 distinct channels (vs 8 at 40% overlap), i.e. more
    independent weights to learn. At 300 trials the median <R> was still
    rising monotonically (0.36/0.46/0.62/0.71/0.76/0.81 per 50-trial
    window, seed 2) and the trailing-80 mean landed at 0.796 — an
    under-trained test budget, not a convergence bug. With the same 450
    trials the fig11 test uses it reaches 0.865 (seed 2) / 0.915 (seed 3).
    """
    ecfg = RSTDPConfig(overlap=0.0)
    out, state, meta = run_training(n_trials=450, seed=2, ecfg=ecfg)
    assert _trailing(out["mean_reward"], slice(None), n=80) > 0.8


def test_scanned_training_twice_leaves_instance_readable():
    """The scanned program donates its state; a fresh ``init`` must not
    alias the instance's buffers (``e_leak`` seeds the membranes), or the
    first run deletes them. Two runs from ``init`` are bit-identical and
    ``meta["inst"]`` stays readable afterwards."""
    init, _, meta = make_experiment(prefix=(2,),
                                    instance_key=jax.random.PRNGKey(0))
    scanned = make_scanned_training(meta["scanned_training"])
    stims = jnp.asarray([1, 2, 0, 1], jnp.int32)
    runs = [jax.device_get(scanned(init(jax.random.PRNGKey(1)), stims))
            for _ in range(2)]
    for a, b in zip(jax.tree.leaves(runs[0]), jax.tree.leaves(runs[1])):
        np.testing.assert_array_equal(a, b)
    leaves = jax.tree.leaves(meta["inst"])
    assert leaves and all(np.isfinite(np.asarray(x)).all() for x in leaves)
