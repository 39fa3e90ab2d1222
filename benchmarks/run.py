"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Quick mode by default (CPU);
``--full`` runs the paper-scale variants of each.

``--json [PATH]`` additionally runs the per-phase attention suite
(`attention_phases.py`) and writes its structured results (default
``BENCH_attention.json`` — the committed perf baseline). When the output
file already exists it is treated as the baseline: a one-line regression
summary is printed (fail-soft WARNING when any phase is >20% slower on the
same platform) before the file is overwritten.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks.common import regression_summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig3,table2,fig6,fig2,"
                         "table1,fig4,attn_phases")
    ap.add_argument("--json", nargs="?", const="BENCH_attention.json",
                    default=None, metavar="PATH",
                    help="run the attention phase suite and write its "
                         "structured results (default BENCH_attention.json);"
                         " prints a fail-soft regression summary against "
                         "the existing file")
    ap.add_argument("--require-tpu", action="store_true",
                    help="abort unless running on real TPU silicon — the "
                         "`make bench-tpu` lane, so compiled-hardware "
                         "numbers never get recorded from an interpret-"
                         "mode host by accident")
    args = ap.parse_args()
    quick = not args.full

    if args.require_tpu:
        import jax
        if jax.default_backend() != "tpu":
            sys.exit("bench: --require-tpu but jax.default_backend() is "
                     f"{jax.default_backend()!r} — run this lane on a TPU "
                     "host (the CPU lane is `make bench-json`)")

    from benchmarks import (attention_phases, fig2_dropout, fig3_scaling,
                            fig4_attnmap, fig6_loss, table1_lra_lite,
                            table2_throughput)

    suites = {
        "fig3": fig3_scaling.run,
        "table2": table2_throughput.run,
        "fig6": fig6_loss.run,
        "fig2": fig2_dropout.run,
        "table1": table1_lra_lite.run,
        "fig4": fig4_attnmap.run,
        "attn_phases": attention_phases.run,
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}
    if args.json:
        # the JSON path subsumes the CSV rows of the phase suite
        suites.pop("attn_phases", None)

    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        t0 = time.time()
        try:
            for row in fn(quick=quick):
                print(row, flush=True)
        except Exception as e:  # noqa: BLE001 — report, then fail the run
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
        print(f"{name}/elapsed,{(time.time() - t0) * 1e6:.0f},",
              flush=True)
    if failed:
        raise SystemExit(f"benchmarks failed: {', '.join(failed)}")

    if args.json:
        fresh = attention_phases.collect(quick=quick)
        for row in attention_phases.rows(fresh):
            print(row, flush=True)
        if os.path.exists(args.json):
            try:
                with open(args.json) as f:
                    baseline = json.load(f)
                print(regression_summary(baseline, fresh), flush=True)
            except (json.JSONDecodeError, OSError) as e:
                print(f"bench-json: baseline unreadable ({e}) — skipping "
                      f"regression check", file=sys.stderr)
        else:
            print("bench-json: no baseline yet — writing first one",
                  flush=True)
        with open(args.json, "w") as f:
            json.dump(fresh, f, indent=2)
            f.write("\n")
        print(f"bench-json: wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
