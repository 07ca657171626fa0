"""One run of one cell: set-up, warm-up, the measured window, the metrics,
and the comparison that decides ``correct``.

The harness drives ``InferenceEngine.submit()`` and ``step()`` itself on
the host clock and stamps each token when ``step()`` returns it: ``step()``
returns only after the device has produced the tokens it emits, so the
stamps time what a user sees (the engine's own stamps are taken before that
sync and are not used).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Optional

import numpy as np

from chipbench import correct, flops, spec, stats, traffic, weights

class NoChip(RuntimeError):
    """No accelerator this benchmark knows, or fewer chips than the cell
    asks for."""


def clock() -> float:
    return time.perf_counter()


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def check_device(chips: int):
    """The first device and its peaks; raises :class:`NoChip` off a TPU
    this benchmark has peaks for, or with fewer than ``chips`` devices."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX found platform {d.platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        return d, flops.peaks(d.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None


class CompileCounter:
    """Counts, through ``jax.monitoring``, programs lowered (each one then
    compiled or read from the persistent cache), and of those the ones read
    from the persistent cache and the ones it missed."""
    LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.n = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, duration, **kw):
        if event == self.LOWERED:
            self.n += 1

    def _on_event(self, event, **kw):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1


def model_config(config: dict):
    from repro.configs.base import ModelConfig
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: v for k, v in config.items() if k in names})


def build_model(cell: spec.Cell):
    """The program's model for the cell's configuration, and the shape of
    its parameter tree."""
    import jax.numpy as jnp
    from repro.models.factory import build_model as build
    model = build(model_config(cell.config))
    return model, model.abstract_params(jnp.bfloat16)


def make_engine(cell: spec.Cell, model, abstract, seed: int):
    """The engine the traffic file states (slots, max_seq, policy, pool),
    serving seeded bf16 weights. Prefill chunk and page size stay the
    program's own defaults. Engines of one model share its compiled
    programs."""
    from repro.serving.engine import InferenceEngine
    params = weights.make_params(abstract, seed,
                                 int(cell.config["vocab_size"]))
    e = cell.traffic["engine"]
    kw = dict(max_slots=int(e["slots"]), max_seq=int(e["max_seq"]),
              policy=e["policy"])
    kv_pages = None
    if e.get("pool_tokens"):
        # the page size is the engine's to choose: ask a one-page engine
        page = InferenceEngine(model, kv_pages=1, **kw).page_size
        kv_pages = math.ceil(int(e["pool_tokens"]) / page)
    engine = InferenceEngine(model, kv_pages=kv_pages, **kw)
    engine.load_params(params)
    return engine


def build_engine(cell: spec.Cell, seed: int):
    model, abstract = build_model(cell)
    return model, abstract, make_engine(cell, model, abstract, seed)


def warm_up(engine, streams: list) -> int:
    """Serve one request per prefill width the traffic will dispatch (and
    at least one per slot, so every slot's programs run), one token each.
    Returns the number of prefill widths warmed."""
    import jax
    from repro.serving.request import Request
    widths = traffic.prefill_widths(streams, engine.prefill_chunk)
    lengths = widths + [widths[-1]] * max(0, engine.max_slots - len(widths))
    rng = np.random.default_rng(0)
    for i, w in enumerate(lengths):
        engine.submit(Request(-(i + 1), rng.integers(
            0, engine.cfg.vocab_size, w).astype(np.int32), 1))
    while engine.waiting or any(r is not None for r in engine.active):
        engine.step()
    jax.block_until_ready(engine.cache)
    engine.done.clear()
    return len(widths)


def annotate(name: str, on: bool):
    import contextlib
    import jax
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


@dataclasses.dataclass
class Window:
    timed: dict               # request_id -> stats.Timed, due in the window
    reqs: dict                # request_id -> engine Request
    seconds: float            # the window's measured length
    exhausted: list           # closed streams that ran out of calls
    late_s: list              # how late each open request was submitted


def drive(engine, streams: list, seconds: float, traced: bool,
          on_close=None) -> Window:
    """The measured window; none is sent after its close. A request that
    is neither finished nor held by the engine (waiting or in a slot) at the
    close is failed: dropped, or cut short. One still held is unfinished:
    late, not failed."""
    import jax
    from repro.serving.request import Request
    opened = sorted(((c.due_s, s, c) for s in streams if s.loop == "open"
                     for c in s.calls), key=lambda x: x[0])
    queues = {s.name: list(s.calls) for s in streams if s.loop == "closed"}
    closed = {s.name: s for s in streams if s.loop == "closed"}
    timed, reqs, late, exhausted = {}, {}, [], []
    rid = [0]
    t0 = clock()
    e0 = engine.now()

    def submit(stream, call, due):
        rid[0] += 1
        dl = (None if stream.deadline_after_s is None
              else e0 + due + stream.deadline_after_s)
        r = Request(rid[0], call.prompt, call.max_new, arrival_s=e0 + due,
                    deadline_s=dl, app=stream.name)
        with annotate("bench.submit", traced):
            engine.submit(r)
        reqs[r.request_id] = r
        timed[r.request_id] = stats.Timed(stream.name, due, call.max_new)

    def next_call(stream, due):
        q = queues[stream.name]
        if q:
            submit(stream, q.pop(0), due)
        elif stream.name not in exhausted:
            exhausted.append(stream.name)

    for s in closed.values():
        for _ in range(s.clients):
            next_call(s, 0.0)
    nxt, n_done = 0, 0

    def step(t_close):
        nonlocal n_done
        with annotate("bench.step", traced):
            emitted = engine.step()
        t = clock() - t0
        for r_id, _ in emitted:
            if r_id in timed:
                timed[r_id].token_s.append(t)
        for r in engine.done[n_done:]:
            if r.app in closed and t < t_close:
                next_call(closed[r.app], t)
        n_done = len(engine.done)
        return t

    with annotate("bench.window", traced):
        t = 0.0
        while t < seconds:
            while nxt < len(opened) and opened[nxt][0] <= t:
                due, s, c = opened[nxt]
                submit(s, c, due)
                late.append(t - due)
                nxt += 1
            if engine.waiting or any(r is not None for r in engine.active):
                t = step(seconds)
            else:
                wake = opened[nxt][0] if nxt < len(opened) else seconds
                with annotate("bench.wait", traced):
                    time.sleep(max(0.0, min(wake, seconds) - t))
                t = clock() - t0
        jax.block_until_ready(engine.cache)
        window_s = clock() - t0
    if on_close is not None:
        on_close()
    held = {r.request_id for r in engine.waiting}
    held |= {r.request_id for r in engine.active if r is not None}
    for i, x in timed.items():
        x.failed = not x.done and i not in held
    return Window(timed, reqs, window_s, exhausted, late)


def _stats_delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float)) and not isinstance(
                after[k], bool)}


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        checkout=spec.CHECKOUT, bench_dir=spec.BENCH_DIR,
        t_start: Optional[float] = None) -> dict:
    """One run; returns the result line's object."""
    import jax
    t_start = clock() if t_start is None else t_start
    cell = spec.load_cell(cell_name, checkout, bench_dir)
    readers = {m["name"]: spec.load_reader(m["name"], bench_dir)
               for m in (cell.per_layer if trace else cell.end_to_end)}
    device, peak = check_device(cell.chips)
    compiles = CompileCounter()
    t_build = clock()
    model, abstract, engine = build_engine(cell, seed)
    streams = traffic.generate(cell.traffic, seconds, seed,
                               int(cell.config["vocab_size"]))
    rec = correct.Dispatches(engine)
    t_warm = clock()
    n_widths = warm_up(engine, streams)
    warm_compiles = compiles.n
    stats0 = dataclasses.asdict(engine.stats)
    mark = rec.mark()
    gc.collect()
    gc.disable()
    setup_s = clock() - t_start
    log(f"set-up {setup_s:.3f} s: start {t_build - t_start:.3f} s, "
        f"weights and engine {t_warm - t_build:.3f} s, warm-up "
        f"{clock() - t_warm:.3f} s; {n_widths} prefill widths warmed, "
        f"{warm_compiles} programs lowered, {compiles.hits} read from the "
        f"persistent cache, {compiles.misses} compiled")
    tracer = None
    if trace:
        from chipbench import trace as tracing
        tracer = tracing.Recording(tempfile.mkdtemp(prefix="chipbench-"))
        tracer.start()
    closing = {}

    def on_close():
        if tracer is not None:
            tracer.stop()
        closing["compiles"] = compiles.n - warm_compiles
        closing["stats"] = dataclasses.asdict(engine.stats)
        closing["call"] = rec.mark()

    try:
        window = drive(engine, streams, seconds, trace, on_close=on_close)
    finally:
        gc.enable()
    in_window_compiles = closing["compiles"]
    st = _stats_delta(stats0, closing["stats"])
    reduced = tracer.reduce() if tracer else None
    ms = device.memory_stats() or {}
    ctx = SimpleNamespace(
        cell=cell, seed=seed, setup_s=setup_s, window=window,
        stats=st, peaks=peak, dims=flops.Dense.of(cell.config),
        prefill_programs=n_widths,
        dispatches=rec, first_call=mark, close_call=closing["call"],
        trace=reduced,
        slo={s.name: s.slo for s in streams if s.slo})
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = readers[m["name"]](ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": int(ms.get("peak_bytes_in_use", 0))}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
    attempted = len(window.timed)
    failed = sum(x.failed for x in window.timed.values())
    unfinished = sum(not x.done for x in window.timed.values()) - failed
    log(f"window {window.seconds:.3f} s: {attempted} requests due, "
        f"{unfinished} unfinished at the close, {failed} failed; "
        f"{in_window_compiles} programs lowered inside the window; "
        f"submissions late by up to "
        f"{max(window.late_s, default=0.0):.4f} s")
    if window.exhausted:
        log(f"closed streams ran out of calls: {window.exhausted}")
    compared = check(cell, seed, abstract, engine, rec, mark, window)
    result = {"correct": passes(compared), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if reduced is not None:
        result["breakdown"] = reduced.breakdown
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    return result


def fed_sample(seed, rec, mark, window) -> tuple:
    """The sampled finished requests, what each was fed (rebuilt from the
    calls since ``mark``), and the faults found in rebuilding."""
    finished = [window.reqs[i] for i, x in window.timed.items() if x.done]
    picked = correct.sample(finished, seed)
    ids = {r.request_id for r in picked}
    rows = [row for i, call in enumerate(rec.calls[mark:])
            if ids & set(call.holders)
            for row in rec.rows(mark + i, mark + i + 1)
            if row[1] in ids]
    fed = correct.rebuild(rows)
    return picked, fed, correct.check_sample(picked, fed)


def free(engine) -> None:
    """Drops the program's device state (weights, KV pool)."""
    engine.cache = engine.params = engine._fresh_slot = None
    gc.collect()


def passes(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def check(cell, seed, abstract, engine, rec, mark, window,
          control: bool = False) -> dict:
    """The numbers ``correct`` compares, each with its limit. The reference
    runs once the program's state is freed. With ``control``, the control
    stands in the program's place: at each position where the program
    served a token, the token that the reference computed in float8 puts
    first is the one judged."""
    from chipbench import reference
    picked, fed, faults = fed_sample(seed, rec, mark, window)
    free(engine)
    gap = 0.0
    if picked and not faults:
        specs = weights.leaf_specs(abstract)
        seqs = [(fed[r.request_id].tokens, fed[r.request_id].out_positions)
                for r in picked]
        ref = reference.logits_at(cell.config, specs, seed, seqs)
        if control:
            gaps = correct.control_gaps(ref, reference.logits_at(
                cell.config, specs, seed, seqs, control=True))
        else:
            gaps = correct.served_gaps(picked, ref)
        gap = float(max(g.max() for g in gaps))
    if not picked:
        log("no request finished inside the window: nothing to compare")
    for f in faults:
        log(f"fault: {f}")
    failed = sum(x.failed for x in window.timed.values())
    served = sum(len(r.tokens_out) for r in picked)
    log(f"compared {len(picked)} requests, {served} served tokens"
        + (" (the float8 control in the program's place)" if control
           else ""))
    limit = float(cell.config["check"]["max_logit_gap"])
    return {
        "no_sample": {"value": int(not picked), "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
        "fed_faults": {"value": len(faults), "limit": 0},
        "max_logit_gap": {"value": gap, "limit": limit},
    }
