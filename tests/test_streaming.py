"""Streaming observability: quantile sketches vs exact percentiles, the
online pipeline vs the post-hoc report, per-request critical-path
assembly (completeness, partition invariant, cross-substrate parity),
ring-buffer recorder bounds, the schema-1.8 attribution block and the ICI
roofline term."""
import json
import math
import random

import pytest

from repro.bench import Scenario, ScenarioApp
from repro.resilience.degradation import SloTracker
from repro.roofline.analysis import achieved_fraction
from repro.roofline.hw import TPU_V5E
from repro.telemetry import (BUCKETS, RequestAssembler,
                             StreamingPipeline, TraceRecorder,
                             attribution_from_trace,
                             empty_attribution_block)
from repro.telemetry.streaming import GKSketch, P2Quantile, _interp_sorted

SUBSTRATES = ("simulator", "engine")


def _concurrent(substrate, *, telemetry=True, **kw):
    return Scenario(
        name="stream", mode="concurrent", policy="slo_aware",
        total_chips=64, substrate=substrate, telemetry=telemetry, seed=1,
        apps=[ScenarioApp("chatbot", num_requests=3),
              ScenarioApp("live_captions", num_requests=4)], **kw)


def _exact_q(vals, q):
    return _interp_sorted(sorted(vals), q)


# --------------------------------------------------------------- sketches
def test_gk_sketch_within_one_percent_of_exact():
    rng = random.Random(7)
    vals = [rng.lognormvariate(0.0, 1.0) for _ in range(10_000)]
    sk = GKSketch(eps=0.0005)
    for v in vals:
        sk.add(v)
    assert sk.count == len(vals)
    # bounded space: far below the raw stream after compression kicks in
    assert sk.space < len(vals) / 2
    for q in (0.05, 0.25, 0.50, 0.90, 0.99):
        exact = _exact_q(vals, q)
        assert sk.query(q) == pytest.approx(exact, rel=0.01)


def test_gk_sketch_exact_while_uncompressed():
    rng = random.Random(3)
    vals = [rng.uniform(0.0, 5.0) for _ in range(200)]
    sk = GKSketch(eps=0.001)
    for v in vals:
        sk.add(v)
    # below the compression threshold nothing merged: bit-for-bit equal to
    # the numpy-interpolating percentile over the raw order statistics
    for q in (0.1, 0.5, 0.99):
        assert sk.query(q) == _exact_q(vals, q)


def test_p2_quantile_estimator():
    p2 = P2Quantile(0.5)
    for v in (3.0, 1.0, 2.0):        # exact below five observations
        p2.add(v)
    assert p2.value == 2.0
    rng = random.Random(11)
    vals = [rng.lognormvariate(0.0, 0.5) for _ in range(5_000)]
    for v in vals:
        p2.add(v)
    assert p2.value == pytest.approx(_exact_q(vals, 0.5), rel=0.05)
    with pytest.raises(ValueError):
        P2Quantile(1.0)


# ------------------------------------------------- pipeline vs post-hoc
@pytest.mark.parametrize("substrate", SUBSTRATES)
def test_streaming_reproduces_posthoc_metrics(substrate):
    """The pipeline, fed live off the trace bus, must reproduce the
    post-hoc SLOReport numbers: exact counts, quantiles within the sketch
    tolerance (exact here — small run, sketches uncompressed)."""
    res = _concurrent(substrate).run()
    pipe = StreamingPipeline()
    res.sim.trace.replay(pipe)
    for app, report in res.sim.reports.items():
        recs = report.records
        assert pipe.sketches[app]["e2e"].count == len(recs)
        for metric, attr in (("e2e", "e2e_s"), ("ttft", "ttft_s")):
            vals = [getattr(r, attr) for r in recs
                    if getattr(r, attr) is not None]
            if not vals:
                continue
            for q in (0.5, 0.99):
                assert pipe.quantile(app, metric, q) == pytest.approx(
                    _exact_q(vals, q), rel=0.01)
    snap = pipe.snapshot()
    assert snap["issued"] == snap["completed"] == 3 + 4
    assert snap["queue_depth"] == 0 and snap["queue_depth_peak"] > 0


@pytest.mark.parametrize("substrate", SUBSTRATES)
def test_assembler_completeness_and_partition(substrate):
    """Every issued request_id closes exactly once, and the critical-path
    buckets PARTITION each request's wall-clock span to 1e-6."""
    res = _concurrent(substrate).run()
    closed = []
    asm = RequestAssembler(closed.append)
    res.sim.trace.replay(asm)
    counts = res.sim.trace.counts()
    assert counts["arrive"] == 3 + 4
    assert len(closed) == counts["arrive"]      # one terminal per arrive
    assert asm.open_count == 0
    assert len({(lc.app, lc.request_id) for lc in closed}) == len(closed)
    for lc in closed:
        assert sum(lc.breakdown().values()) == pytest.approx(
            lc.total_s, abs=1e-6)
        assert all(v >= -1e-12 for v in lc.breakdown().values())


def test_live_pipeline_matches_posthoc_replay_and_reruns_identically():
    """The live attribution block == a post-hoc replay of the same trace,
    and a seeded rerun serializes byte-identically."""
    res = _concurrent("simulator").run()
    live = res.sim.summary()["attribution"]
    assert live["enabled"] and live["requests"] == 3 + 4
    assert live == attribution_from_trace(res.sim.trace)
    rerun = _concurrent("simulator").run().sim.summary()["attribution"]
    assert (json.dumps(live, sort_keys=True)
            == json.dumps(rerun, sort_keys=True))


def test_work_buckets_agree_across_substrates():
    """prefill/decode/recompute seconds come from the SHARED virtual cost
    model — the substrates must agree on them (the fig_attribution
    parity gate); wait buckets attribute each substrate's own schedule."""
    per = {}
    for substrate in SUBSTRATES:
        at = _concurrent(substrate).run().sim.summary()["attribution"]
        per[substrate] = {
            b: sum(t["seconds"][b] for t in at["per_app"].values())
            for b in BUCKETS}
    for b in ("prefill", "decode", "recompute"):
        a, e = per["simulator"][b], per["engine"][b]
        assert a == pytest.approx(e, rel=0.05, abs=1e-9), b


@pytest.mark.parametrize("substrate", SUBSTRATES)
def test_attribution_block_zero_filled_when_disabled(substrate):
    summary = _concurrent(substrate, telemetry=False).run().sim.summary()
    at = summary["attribution"]
    assert at == empty_attribution_block()
    assert at["enabled"] is False and at["requests"] == 0
    assert at["terminal"] == {"finish": 0, "cancel": 0, "shed": 0}


# ------------------------------------------------------------ ring mode
def test_ring_recorder_bounds_memory_with_exact_aggregates():
    tr = TraceRecorder(ring=256)
    n = 10_000
    for i in range(n):
        t = i * 1e-3
        tr.span("decode", "a", i, t, t + 1e-3, chips=1, tokens=2)
        if i % 100 == 0:
            tr.counter("kv_pages", t, float(i))
    assert len(tr.events) == 256                 # O(window) retained
    assert tr.counts()["decode"] == n            # aggregates stay exact
    assert tr.token_total("decode") == 2.0 * n
    assert tr.makespan_s == pytest.approx((n - 1) * 1e-3 + 1e-3)


def test_ring_scenario_keeps_streaming_attribution_exact():
    """trace_ring bounds the retained trace, but the pipeline subscribed
    LIVE still sees every event: the attribution block stays complete."""
    sc = _concurrent("simulator", trace_ring=16)
    res = sc.run()
    assert len(res.sim.trace.events) <= 16
    at = res.sim.summary()["attribution"]
    assert at["requests"] == 3 + 4 and at["open"] == 0
    # while the post-hoc replay over the truncated window cannot
    assert attribution_from_trace(res.sim.trace)["requests"] < 3 + 4


def test_trace_ring_round_trips_through_scenario_spec():
    sc = _concurrent("simulator", trace_ring=128)
    assert Scenario.from_dict(sc.to_dict()).trace_ring == 128
    assert "trace_ring" not in _concurrent("simulator").to_dict()


# -------------------------------------------------- satellites: roofline
def test_achieved_fraction_ici_roof():
    dur, chips = 1e-3, 4
    base = achieved_fraction(1e9, 1e6, dur, chips, TPU_V5E)
    # an ICI-dominated span (tiny compute, big transfer) hits the ICI roof
    half_link = 0.5 * TPU_V5E.ici_link_bandwidth * dur * chips
    ici = achieved_fraction(1e9, 1e6, dur, chips, TPU_V5E,
                            ici_bytes=half_link)
    assert ici == pytest.approx(0.5) and ici > base
    # clamped to 1, and inert when the chip has no ICI (host CPU)
    assert achieved_fraction(0, 0, dur, chips, TPU_V5E,
                             ici_bytes=10 * half_link) == 1.0


# ------------------------------------------------------ satellite: burn rate
def test_telemetry_block_host_series_zero_filled_without_monitor():
    blk = _concurrent("simulator").run().summary()["concurrent"]["telemetry"]
    assert all(v == 0.0 for v in blk["host_cpu_pct"])
    assert blk["host_rss_mb_peak"] == 0.0


def test_slo_burn_rate():
    tr = SloTracker(window=8)
    for _ in range(8):
        tr.note("a", True)
    assert tr.burn_rate("a", 0.9) == 0.0
    for _ in range(8):
        tr.note("a", False)
    assert tr.burn_rate("a", 0.9) == pytest.approx(10.0)  # miss=1, budget=.1
    assert tr.burn_rate("a", 1.0) == 8.0    # no budget: capped to window
    pipe = StreamingPipeline(slo_target=0.9)
    pipe.bind_tracker(tr)
    assert pipe.burn_rate("a") == pytest.approx(10.0)


def test_burn_rate_reads_the_shed_controllers_window():
    """With shed_on_slo active the pipeline binds the controller's own
    tracker — one rolling-SLO truth feeding both shedding and burn rate."""
    sc = _concurrent(
        "simulator",
        faults=[{"kind": "client_timeout", "timeout_s": 0.05,
                 "max_retries": 1}],
        shed_on_slo={"attainment": 0.99, "window": 4})
    res = sc.run()
    at = res.sim.summary()["attribution"]
    term = at["terminal"]
    assert at["requests"] == 3 + 4               # sheds close lifecycles too
    assert term["finish"] + term["cancel"] + term["shed"] == at["requests"]
