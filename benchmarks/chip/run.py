#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload stablelm-3b.chat --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout on a machine with a TPU. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number the correctness check
compared, with its limit. Without a TPU listed in ``chipbench.flops.PEAKS``,
or with fewer chips than the cell asks for, it exits with code 2 and prints
no result.

JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``, a
fixed path, so that only a checkout's first run of a cell compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # set before JAX is imported, so that JAX and the program's own
    # enable_compile_cache() both take this directory
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from repro.launch.compile_cache import enable_compile_cache
    from chipbench import harness
    enable_compile_cache()
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"[chipbench] no chip: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
