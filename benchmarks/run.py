# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
#   python benchmarks/run.py            # full measurement run
#   python benchmarks/run.py --smoke    # tiny request counts: CI import check
#   python benchmarks/run.py --only fig5_concurrent,fig7_workflow
#   python benchmarks/run.py --smoke --only kernel_bench,engine_bench \
#       --json BENCH_kernels.json       # CI perf-trajectory artifact
from __future__ import annotations

import argparse
import contextlib
import json
import platform
import signal
import sys
import time
import traceback

BENCH_SCHEMA_VERSION = 1

#: --smoke default for --row-timeout: a hung benchmark row fails fast with
#: its suite named instead of stalling CI until the job-level kill
SMOKE_ROW_TIMEOUT_S = 120.0


class RowTimeout(Exception):
    """A benchmark suite exceeded the per-row wall-clock budget."""


@contextlib.contextmanager
def row_deadline(suite: str, seconds: float):
    """Raise :class:`RowTimeout` (naming the suite) if the body runs longer
    than ``seconds``. SIGALRM-based, so it interrupts a wedged row rather
    than waiting for it; no-op where SIGALRM is unavailable (Windows) or
    the budget is 0."""
    if seconds <= 0 or not hasattr(signal, "SIGALRM"):
        yield
        return

    def _alarm(signum, frame):
        raise RowTimeout(f"suite {suite!r} exceeded the per-row "
                         f"{seconds:g}s timeout")

    prev = signal.signal(signal.SIGALRM, _alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


def _write_json(path: str, suites: list[tuple[str, list[str]]],
                smoke: bool) -> None:
    """Versioned bench document (the perf trajectory CI uploads per PR)."""
    entries = []
    for suite, lines in suites:
        for line in lines:
            name, us, derived = line.split(",", 2)
            entries.append({"suite": suite, "name": name,
                            "us_per_call": float(us), "derived": derived})
    doc = {
        "version": BENCH_SCHEMA_VERSION,
        "smoke": smoke,
        "python": platform.python_version(),
        "machine": platform.machine(),
        "entries": entries,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {len(entries)} entries to {path}", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="run every figure with tiny request counts "
                         "(fast import-and-run check, not a measurement)")
    ap.add_argument("--only", default="",
                    help="comma-separated suite names to run")
    ap.add_argument("--json", default="",
                    help="also write collected rows to this path as a "
                         "versioned JSON document (perf-trajectory artifact)")
    ap.add_argument("--substrate", default="simulator",
                    choices=("simulator", "engine"),
                    help="execution substrate for Scenario-declared "
                         "figures: the analytic pod simulator (default) or "
                         "the real InferenceEngine under a virtual cost "
                         "clock")
    ap.add_argument("--row-timeout", type=float, default=None,
                    help="wall-clock seconds each suite may spend producing "
                         "a row before it is failed with RowTimeout (0 "
                         "disables; default: 0, or "
                         f"{SMOKE_ROW_TIMEOUT_S:.0f} under --smoke)")
    args = ap.parse_args(argv)
    row_timeout = args.row_timeout
    if row_timeout is None:
        row_timeout = SMOKE_ROW_TIMEOUT_S if args.smoke else 0.0

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import common
    if args.smoke:
        common.enable_smoke()
    common.set_substrate(args.substrate)

    from benchmarks import (appendix_platforms, engine_bench, fig3_exclusive,
                            fig4_utilization, fig5_concurrent, fig6_sharing,
                            fig7_workflow, fig_attribution, fig_memory,
                            fig_prefix, fig_resilience, fig_routing,
                            fig_stallfree, kernel_bench, roofline_table,
                            telemetry_bench)
    suites = [
        ("fig3_exclusive", fig3_exclusive.run),
        ("fig4_utilization", fig4_utilization.run),
        ("fig5_concurrent", fig5_concurrent.run),
        ("fig6_sharing", fig6_sharing.run),
        ("fig7_workflow", fig7_workflow.run),
        ("fig_attribution", fig_attribution.run),
        ("fig_memory", fig_memory.run),
        ("fig_prefix", fig_prefix.run),
        ("fig_resilience", fig_resilience.run),
        ("fig_routing", fig_routing.run),
        ("fig_stallfree", fig_stallfree.run),
        ("appendix_platforms", appendix_platforms.run),
        ("engine_bench", engine_bench.run),
        ("telemetry_bench", telemetry_bench.run),
        ("kernel_bench", kernel_bench.run),
        ("roofline_table", roofline_table.run),
    ]
    if args.only:
        keep = {s.strip() for s in args.only.split(",") if s.strip()}
        known = {n for n, _ in suites}
        unknown = sorted(keep - known)
        if unknown:
            ap.error(f"unknown suite(s) {', '.join(unknown)}; "
                     f"available: {', '.join(sorted(known))}")
        suites = [(n, fn) for n, fn in suites if n in keep]

    print("name,us_per_call,derived")
    failures = []
    collected: list[tuple[str, list[str]]] = []
    for name, fn in suites:
        t0 = time.time()
        lines: list[str] = []
        collected.append((name, lines))  # keep partial rows on failure
        try:
            # the deadline is re-armed per row, so generator-style suites
            # get a true per-row budget; list-returning suites spend it all
            # producing the first "row" (the whole list)
            with row_deadline(name, row_timeout):
                it = iter(fn())
            while True:
                with row_deadline(name, row_timeout):
                    line = next(it, None)
                if line is None:
                    break
                print(line, flush=True)
                lines.append(line)
        except RowTimeout as e:
            failures.append(name)
            print(f"{name}_TIMEOUT,0.0,{e}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            print(f"{name}_FAILED,0.0,{e!r}", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if args.json:
        _write_json(args.json, collected, args.smoke)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
