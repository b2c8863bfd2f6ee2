"""Readings that set a cell's limits: the program's sound runs and the
control, on the chip, at the cell's own size.

  python3 bench/control.py --workload <cell> [--seeds 12] [--first N]

For each seed the cell's compiled program runs its first call from a
fresh ``init`` (the call set-up drives in a benchmark run), and its first
trials are compared with the reference, as a run compares them (the
lower readings). The control puts the reference in the program's place,
computed in the precision below the one the configuration states (matmuls
at "high" or better): its synaptic matmul operands in one bfloat16 term
("default"), compared with the reference the same way (the upper
readings). "high" itself, the least the configuration allows, is read
beside it and has to read like a sound run. The
altered-answer fault is read on the program's own output. All seeds
share one process, so the program compiles once. Prints one JSON object
per seed and a summary; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[0:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first", type=int, default=3_000_000_001)
    ap.add_argument("--precision", nargs="+", default=["default", "high"])
    args = ap.parse_args(argv)

    import jax
    from bench import run as brun
    from bench.harness import check, s5
    from bench.harness.driver import against_reference, reference_networks
    from bench.harness.keys import run_key
    from repro.compile_cache import enable_compile_cache

    spec = brun.load_spec(args.workload)
    if jax.devices()[0].platform != "tpu" or \
            len(jax.devices()) < int(spec["workload"]["chips"]):
        print("FAIL: needs the cell's TPU chips", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    config, traffic, limits = spec["config"], spec["traffic"], spec["limits"]
    kind = s5.kind_of(config)
    n = int(limits["check_trials"])
    cell = s5.build(config, traffic)
    runs = []
    for i in range(args.seeds):
        seed = args.first + 7919 * i
        sample, calls = None, [0]
        if kind == "fleet":
            sample = check.sample_instances(seed, cell.prefix[0],
                                            int(limits["check_instances"]))
        if cell.fresh:
            calls += check.sample_calls(seed, int(limits["check_span"]),
                                        int(limits["check_experiments"]) - 1)
        progs = {}
        for c in calls:
            state, hist = cell.scanned(cell.init(run_key(seed, c)),
                                       cell.stims)
            progs[c] = s5.check_slice(hist, n, sample)
            del state, hist
        runs.append((seed, sample, progs))
    del cell
    out = dict(workload=args.workload, sound=[], control=[], fault=[])
    for i, (seed, sample, progs) in enumerate(runs):
        prog, ref = against_reference(config, traffic, seed, progs, sample, n)
        row = dict(seed=seed, **check.numbers(prog, ref),
                   **check.diagnostics(prog, ref),
                   spikes=float(prog["rates"].sum()))
        out["sound"].append(row)
        print(json.dumps(dict(kind="sound", **row)), flush=True)
        if i >= args.control_seeds:
            continue
        # the altered-answer fault: the second trial's spike counts are
        # the first trial's, in every checked network
        bad = dict(prog, rates=prog["rates"].copy())
        bad["rates"][1] = bad["rates"][0]
        frow = dict(seed=seed, fault="altered_answer",
                    **check.numbers(bad, ref))
        out["fault"].append(frow)
        print(json.dumps(dict(kind="fault", **frow)), flush=True)
        for prec in args.precision:
            low = reference_networks(config, traffic, seed, sorted(progs),
                                     sample, n, precision=prec)
            crow = dict(seed=seed, precision=prec,
                        **check.numbers(low, ref),
                        **check.diagnostics(low, ref))
            out["control"].append(crow)
            print(json.dumps(dict(kind="control", **crow)), flush=True)
    summary = {k: dict(lower=max(r[k] for r in out["sound"]),
                       upper={p: min((r[k] for r in out["control"]
                                      if r["precision"] == p),
                                     default=None)
                              for p in args.precision},
                       fault=min((r[k] for r in out["fault"]),
                                 default=None))
               for k in check.NUMBERS}
    out["summary"] = summary
    print(json.dumps(dict(kind="summary", workload=args.workload,
                          readings=summary)))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"control_{args.workload}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
