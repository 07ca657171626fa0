#!/usr/bin/env python3
"""Smoke test of the serving main path on a TPU, at full width.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # four replicas behind the router

One chip: serves stablelm-3b (32 layers, d_model 2560, seeded random bf16
weights) through ``repro.launch.serve.main`` under the ``chunked`` and the
``mixed`` policy, checks that every request finished with its token count,
that the compiled paged decode and prefill programs hold Pallas kernels
(``tpu_custom_call``), and that one prefill chunk and one decode step give
the same logits through the Pallas kernels and through the jnp lowering.

``--four-chips``: four stablelm-3b replicas, one per device, behind
``serving.router.Router`` in this one process; their token streams must
equal those of one replica serving the same requests, and each device must
hold a replica's parameters.

Any failed check exits non-zero. Without a TPU the script exits non-zero
before it serves anything. The last line of standard output is one JSON
object naming the device. Times printed here are host-clock set-up and
compile seconds, not device metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "stablelm-3b"
SLOTS, MAX_SEQ, CHUNK, MAX_NEW, REQUESTS, SEED = 4, 128, 16, 16, 4, 0
#: Pallas vs jnp logits: max |difference| over max |jnp logit|. Both paths
#: run bf16 weights and activations with float32 attention arithmetic; they
#: differ in the order of the softmax sums and in where RoPE is applied,
#: i.e. by bf16 rounding (2**-8 relative) carried through 32 layers.
LOGITS_RTOL = 5e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check_backend() -> None:
    from repro.kernels import ops
    env = os.environ.get("REPRO_KERNEL_BACKEND", "auto")
    if env not in ("auto", "pallas"):
        fail(f"REPRO_KERNEL_BACKEND={env} would bypass the Pallas kernels")
    if ops.backend() != "pallas":
        fail(f"kernel backend is {ops.backend()!r}, not 'pallas'")
    log("kernel backend pallas")


def serve_phase(policy: str):
    """One ``repro.launch.serve`` run; returns the engine it served with."""
    from repro.launch import serve
    t0 = time.monotonic()
    engine = serve.main(["--arch", ARCH, "--requests", str(REQUESTS),
                         "--max-new", str(MAX_NEW), "--policy", policy,
                         "--slots", str(SLOTS), "--max-seq", str(MAX_SEQ),
                         "--prefill-chunk", str(CHUNK),
                         "--seed", str(SEED)])
    wall = time.monotonic() - t0
    done = engine.done
    short = [(r.request_id, len(r.tokens_out)) for r in done
             if len(r.tokens_out) != r.max_new_tokens]
    if len(done) != REQUESTS or short:
        fail(f"{policy}: {len(done)}/{REQUESTS} requests done, "
             f"short token counts {short}")
    log(f"serve policy={policy}: {len(done)}/{REQUESTS} requests done, "
        f"{MAX_NEW} tokens each; prefill_dispatches="
        f"{engine.stats.prefill_dispatches} "
        f"set-up+compile+serve host seconds={wall}")
    return engine


def logits_phase(engine) -> None:
    """One prefill chunk then one decode step, Pallas vs jnp, on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops

    model, params = engine.model, engine.params
    page = engine.page_size
    nb = -(-MAX_SEQ // page)
    tables = jnp.arange(SLOTS * nb, dtype=jnp.int32).reshape(SLOTS, nb)
    rng = np.random.default_rng(SEED)
    tokens = jnp.asarray(rng.integers(0, model.cfg.vocab_size,
                                      (SLOTS, CHUNK)), jnp.int32)
    start = jnp.zeros((SLOTS,), jnp.int32)
    active = jnp.ones((SLOTS,), bool)

    def run(backend: str):
        ops.set_backend(backend)
        try:
            cache = model.init_paged_cache(SLOTS * nb, page, SLOTS, MAX_SEQ)
            out = {}
            # fresh function objects: the backend is read while tracing
            prefill = jax.jit(lambda p, c, t, s, b, a:
                              model.prefill_chunk_paged(p, c, t, s, b, a))
            decode = jax.jit(lambda p, c, t, ln, b, a:
                             model.decode_step_paged(p, c, t, ln, b, a))
            for name, fn, args in (
                    ("prefill", prefill,
                     lambda c: (params, c, tokens, start, tables, active)),
                    ("decode", decode,
                     lambda c: (params, c, tokens[:, -1:], start + CHUNK,
                                tables, active))):
                t0 = time.monotonic()
                compiled = fn.lower(*args(cache)).compile()
                secs = time.monotonic() - t0
                kernel = "tpu_custom_call" in compiled.as_text()
                log(f"{backend} {name}: compile host seconds={secs} "
                    f"tpu_custom_call={kernel}")
                if backend == "pallas" and not kernel:
                    fail(f"compiled {name} program holds no Pallas kernel")
                logits, cache = compiled(*args(cache))
                out[name] = np.asarray(jnp.asarray(logits, jnp.float32))
            return out
        finally:
            ops.set_backend(None)

    got, want = run("pallas"), run("jnp")
    for name in ("prefill", "decode"):
        g, w = got[name], want[name]
        if g.shape != w.shape or not np.all(np.isfinite(g)):
            fail(f"{name} logits: shape {g.shape} vs {w.shape}, "
                 f"finite={bool(np.all(np.isfinite(g)))}")
        rel = float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1e-30))
        agree = float(np.mean(np.argmax(g, -1) == np.argmax(w, -1)))
        log(f"{name} logits {g.shape}: max|pallas-jnp|/max|jnp|={rel} "
            f"(tolerance {LOGITS_RTOL}); argmax agreement={agree}")
        if not rel <= LOGITS_RTOL:
            fail(f"{name} logits differ by {rel} > {LOGITS_RTOL}")


def four_chip_phase() -> None:
    """Four replicas behind the router vs one replica, same requests."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    from repro.bench.policy import PartitionPlan
    from repro.configs.registry import get_config
    from repro.launch.serve import init_params
    from repro.models.factory import build_model
    from repro.serving.engine import InferenceEngine
    from repro.serving.request import chat_trace
    from repro.serving.router import RouteRequest, Router

    devices = jax.devices()
    if len(devices) != 4:
        fail(f"--four-chips needs 4 devices, JAX found {len(devices)}")
    cfg = get_config(ARCH)
    model = build_model(cfg)
    t0 = time.monotonic()
    params0 = init_params(model, SEED)
    replicas = [jax.device_put(params0, d) for d in devices]
    jax.block_until_ready(replicas)
    log(f"4 replicas placed: host seconds={time.monotonic() - t0}")
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params0))

    def engine_on(params):
        eng = InferenceEngine(model, max_slots=SLOTS, max_seq=MAX_SEQ,
                              policy="chunked", prefill_chunk=CHUNK)
        eng.load_params(params)
        return eng

    def trace():
        return chat_trace(2 * len(devices), cfg.vocab_size, mean_prompt=24,
                          max_new=MAX_NEW, seed=SEED)

    plan = PartitionPlan(apps={"chat": "llm"}, chips={"llm": len(devices)},
                         replicas=len(devices))
    router = Router(plan, "round_robin")
    engines = {label: engine_on(p)
               for label, p in zip(router.labels_for("llm"), replicas)}
    for (label, eng), d in zip(engines.items(), devices):
        held = {x for leaf in jax.tree.leaves((eng.params, eng.cache))
                for x in leaf.devices()}
        if held != {d}:
            fail(f"replica {label} meant for device {d.id} holds arrays on "
                 f"{sorted(x.id for x in held)}")
    log(f"replicas {list(engines)}: parameters and KV cache each on its "
        f"own device {[d.id for d in devices]}")
    for req in trace():
        label = router.route("llm", RouteRequest(
            app="chat", request_id=req.request_id,
            tokens=len(req.prompt) + req.max_new_tokens,
            prompt=[int(t) for t in req.prompt]))
        engines[label].submit(req)
    t0 = time.monotonic()
    with ThreadPoolExecutor(len(engines)) as pool:
        runs = [pool.submit(e.run) for e in engines.values()]
        routed = {r.request_id: list(r.tokens_out)
                  for f in runs for r in f.result()}
    log(f"router: {router.routing_block()['per_replica_load']} "
        f"host seconds={time.monotonic() - t0}")

    one = engine_on(replicas[0])
    for req in trace():
        one.submit(req)
    alone = {r.request_id: list(r.tokens_out) for r in one.run()}
    if sorted(routed) != sorted(alone):
        fail(f"requests served: routed {sorted(routed)} vs one replica "
             f"{sorted(alone)}")
    diff = [i for i in alone if routed[i] != alone[i]]
    if diff or any(len(t) != MAX_NEW for t in alone.values()):
        fail(f"token streams differ from one replica for requests {diff}")
    log(f"{len(alone)} token streams through 4 replicas identical to one "
        f"replica ({MAX_NEW} tokens each)")
    for d in devices:
        st = d.memory_stats()
        log(f"device {d.id} ({d.device_kind}): bytes_in_use="
            f"{st['bytes_in_use']} peak_bytes_in_use="
            f"{st['peak_bytes_in_use']} replica param bytes={param_bytes}")
        if st["bytes_in_use"] < param_bytes:
            fail(f"device {d.id} holds {st['bytes_in_use']} bytes, less "
                 f"than one replica's {param_bytes}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica router phase")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repository source under {ROOT / 'src'}")

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r}")

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache {cache_dir}: {entries} entries at start")
    check_backend()

    if args.four_chips:
        four_chip_phase()
    else:
        serve_phase("chunked")
        engine = serve_phase("mixed")
        logits_phase(engine)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
