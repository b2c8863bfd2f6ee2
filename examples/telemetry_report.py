"""One telemetered training run -> a structured run report.

The commissioning loop the paper's verification methods feed ("From Clean
Room to Machine Room") starts from exactly this artifact: a short §5
training with the jit-safe counter pytree enabled, its host time, the
specializer-cache stats — merged with config and git provenance into
JSON + markdown under ``results/``. Where each layer's device time goes
is a profiler trace's to say: ``repro.obs.timing.profiler_trace`` with
the program's layer scopes (docs/telemetry.md).

Run:  PYTHONPATH=src python examples/telemetry_report.py \
          [--trials N] [--json PATH] [--md PATH] [--rule vm|python]

The tier-2 CI observability job runs this as its smoke test and uploads
the JSON report as a build artifact.
"""
import argparse
import os

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=30)
    ap.add_argument("--rule", default="vm", choices=("vm", "python"),
                    help="plasticity implementation (vm exercises the "
                         "PPU-VM counters and the specializer cache)")
    ap.add_argument("--json", default=None, metavar="PATH")
    ap.add_argument("--md", default=None, metavar="PATH")
    args = ap.parse_args()

    import jax
    from repro.core.hybrid import run_training
    from repro.obs import report as obs_report
    from repro.obs.timing import CacheDelta, PhaseTimer

    # --- the run, counters ON, cache delta and host time captured --------
    timer = PhaseTimer()
    with CacheDelta(warn=False) as cd, timer.span("run_training"):
        out, state, meta = run_training(n_trials=args.trials, seed=0,
                                        rule_impl=args.rule,
                                        telemetry=True)
    tele = out["telemetry"]
    mr = float(np.median(out["mean_reward"][-1]))

    # --- merge + persist -------------------------------------------------
    rep = obs_report.build_report(
        "telemetry_demo", telemetry=tele, timings=timer.summary(),
        cache=dict(cd.delta),
        config=dict(n_trials=args.trials, rule_impl=args.rule,
                    jax_devices=len(jax.devices())),
        extra=dict(median_reward_final=mr))
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "results")
    json_path = args.json or os.path.join(out_dir,
                                          "REPORT_telemetry_demo.json")
    paths = obs_report.write_report(rep, json_path, args.md)
    print(obs_report.to_markdown(rep))
    print(f"wrote {paths['json']} and {paths['md']}")

    # the acceptance invariant, asserted so CI fails loudly: a telemetered
    # run reports real activity
    assert tele["out_spikes"] > 0 and tele["steps"] > 0
    assert tele["trials"] == args.trials
    if args.rule == "vm":
        assert tele["vm_runs"] == args.trials
    return paths


if __name__ == "__main__":
    main()
