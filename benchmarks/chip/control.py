#!/usr/bin/env python3
"""Readings for the limit of ``correct``: the program's widest served-token
gap and its control's, for a cell, over many seeds in one process.

    python3 benchmarks/chip/control.py --workload stablelm-3b.chat \
        --seeds 101-112 --seconds 51 [--control-seeds 3] [--out FILE]

For each seed: the cell's engine serves that seed's weights and traffic for
a window at the cell's own load (as a benchmark run does, with the compiled
programs of the first seed reused), then the harness's own comparison judges
what it served. For the first ``--control-seeds`` seeds the same comparison
runs again with the control in the program's place: at every position where
the program served a token, the token that the reference computed with its
projections in float8 e4m3 puts first. Each comes out with its ``correct``
and its numbers beside their limits, one JSON line per seed to standard
output (and to ``--out``). A window that finishes no request has nothing to
compare and is not correct. The benchmark's own runs never run this; it
sets the limit in each configuration's ``check``.
"""
import argparse
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def readings(cell_name, seed_list, seconds, control_seeds,
             checkout=None, bench_dir=None):
    """Yields one dict of readings per seed."""
    from chipbench import correct, harness, spec, traffic
    kw = {} if checkout is None else dict(checkout=checkout,
                                          bench_dir=bench_dir)
    cell = spec.load_cell(cell_name, **kw)
    harness.check_device(cell.chips)
    model, abstract = harness.build_model(cell)
    vocab = int(cell.config["vocab_size"])
    for n, seed in enumerate(seed_list):
        engine = harness.make_engine(cell, model, abstract, seed)
        streams = traffic.generate(cell.traffic, seconds, seed, vocab)
        rec = correct.Dispatches(engine)
        if n == 0:
            harness.warm_up(engine, streams)
        mark = rec.mark()
        window = harness.drive(engine, streams, seconds, False)
        row = {"seed": seed, "due": len(window.timed),
               "unfinished": sum(not x.done and not x.failed
                                 for x in window.timed.values()),
               "failed": sum(x.failed for x in window.timed.values())}
        runs = [("program", False)] + (
            [("control", True)] if n < control_seeds else [])
        for name, control in runs:
            compared = harness.check(cell, seed, abstract, engine, rec, mark,
                                     window, control=control)
            row[name] = {"correct": harness.passes(compared),
                         "compared": compared}
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112,200")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = open(args.out, "a") if args.out else None
    try:
        for row in readings(args.workload, seeds(args.seeds), args.seconds,
                            args.control_seeds):
            line = json.dumps(row)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
