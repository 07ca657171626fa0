"""Serving launcher: run the continuous-batching engine with a request trace.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --requests 8 --policy chunked

Parameters are random, made in bf16 from ``--seed`` inside one jitted call,
so no float32 copy of the model ever exists on the device. The printed
counts come from the engine. Its decode stamps follow the token fetch, but
prefill stamps only the dispatch, so no latency is printed here; the
engine's phase spans appear in a ``jax.profiler`` trace taken around
``main`` (docs/telemetry.md).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.bench.policy import available_policies
from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.factory import build_model
from repro.serving.engine import InferenceEngine
from repro.serving.request import chat_trace


def init_params(model, seed: int):
    """Seeded random bf16 parameters, built on the default device under
    ``jax.jit`` (the float32 draws stay per-leaf temporaries)."""
    return jax.jit(lambda key: model.init(key, jnp.bfloat16))(
        jax.random.key(seed))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--policy", default="chunked",
                    choices=available_policies())
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = init_params(model, args.seed)

    engine = InferenceEngine(model, max_slots=args.slots,
                             max_seq=args.max_seq, policy=args.policy,
                             prefill_chunk=args.prefill_chunk)
    engine.load_params(params)
    for req in chat_trace(args.requests, cfg.vocab_size,
                          mean_prompt=24, max_new=args.max_new,
                          seed=args.seed):
        engine.submit(req)
    t0 = time.monotonic()
    done = engine.run()
    wall = time.monotonic() - t0
    st = engine.stats
    print(f"[serve] arch={cfg.name} policy={args.policy} done={len(done)} "
          f"decode_tokens={st.decode_tokens} "
          f"prefill_tokens={st.prefill_tokens} "
          f"prefill_dispatches={st.prefill_dispatches} steps={st.steps} "
          f"run_wall_s={wall} (host clock, compiles included)")
    return engine


if __name__ == "__main__":
    main()
