"""Benchmark harness — one entry per paper figure/claim + framework perf.

  fig4_calibration     paper Fig. 4  (MC calibration narrows STP offsets)
  fig8_event_interface paper Fig. 8  (event-bus integrity, adapted)
  fig11_rstdp          paper Fig. 11 (R-STDP reward -> ~1 @ 40% overlap)
  step_time            paper §5     (290us claim: scan vs dispatch vs host)
  kernels              Pallas hot-spot microbenchmarks
  ppuvm                PPU-VM executor ladder (scan / specialized /
                       pallas) vs the fixed-function rule; the ladder is
                       emitted under ``executor_ladder`` in --json output
                       (plus the specializer-cache hit/miss/eviction
                       delta over the bench)
  telemetry            observability overhead ladder: scanned training
                       with the jit-safe counter pytree off vs on
                       (paired-median), counter summary, phase split,
                       and a run report under results/
  wafer                multi-chip weak scaling + routed events/s vs the
                       ~0.4M events/s bus budget
  faults               defect-tolerance sweep: §5 reward vs injected
                       fault rate, naive vs screened+blacklisted, plus
                       the dead-link failover accounting
  mapper               network-mapper compile time vs size, ring relay
                       overhead vs fan-in, mapped-vs-monolithic
                       step-time ratio
  roofline             §Roofline table from the dry-run artifacts

Usage:
  PYTHONPATH=src python -m benchmarks.run [suite] [--json BENCH_x.json]

``--json`` persists the machine-readable results (the bench trajectory
across PRs); without it results are print-only.
"""
import argparse
import json
import sys
import time
import traceback

# provenance + serialization shared with the run-report subsystem: BENCH_*
# trajectory files and results/REPORT_* carry the same header fields
from repro.obs.report import host_header as _host_header
from repro.obs.report import jsonable as _jsonable


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig4_calibration, fig8_event_interface,
                            fig11_rstdp, step_time, faults_bench,
                            kernels_bench, mapper_bench, ppuvm_bench,
                            roofline_table, wafer_bench)
    suites = [
        ("fig4_calibration", fig4_calibration.run),
        ("fig8_event_interface", fig8_event_interface.run),
        ("fig11_rstdp", fig11_rstdp.run),
        ("step_time", step_time.run),
        ("kernels", kernels_bench.run),
        ("ppuvm", ppuvm_bench.run),
        ("wafer", wafer_bench.run),
        ("faults", faults_bench.run),
        ("mapper", mapper_bench.run),
        ("roofline", roofline_table.run),
    ]
    ap = argparse.ArgumentParser()
    ap.add_argument("only", nargs="?", default=None,
                    help="run a single suite by name")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="persist machine-readable results to PATH")
    args = ap.parse_args()
    results = []
    failed = 0
    for name, fn in suites:
        if args.only and args.only != name:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.perf_counter()
        try:
            r = fn() or {}
            r.setdefault("name", name)
            r["seconds"] = round(time.perf_counter() - t0, 2)
            results.append(r)
        except Exception:
            failed += 1
            traceback.print_exc()
    print("\n# name,us_per_call,derived")
    for r in results:
        us = r.get("fused_us") or r.get("seconds", 0) * 1e6
        derived = {k: v for k, v in r.items()
                   if k not in ("name", "seconds")}
        print(f"{r['name']},{us:.1f},{derived}")
    if args.json:
        payload = dict(timestamp=time.strftime("%Y-%m-%dT%H:%M:%S"),
                       argv=sys.argv[1:], **_host_header(), failed=failed,
                       results=_jsonable(results))
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {args.json}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
