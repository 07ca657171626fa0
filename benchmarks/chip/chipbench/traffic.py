"""The one traffic generator: reads a traffic file's streams and draws every
request from ``--seed``.

A stream is open (requests due on a schedule, Poisson arrivals at
``rate_per_s``) or closed (``clients`` callers, each sending its next call
when the last returns, with no think time). Lengths are lognormal around a
``median`` with shape ``sigma``, clipped to ``[min, max]``.

Every seed gets the same work: lengths and inter-arrival gaps are the
stratified quantiles ``(i + 0.5) / n`` of their distributions, put in one
order drawn from the stream's name, and the seed draws only the token ids.
(With the seed shuffling the order too, tokens per second in a saturated
window moved by 19% from seed to seed and by 0.2% between two runs of one
seed: which requests fit in the window was the work.) So the set of prefill
widths a run dispatches is fixed by the file and the window.
The exponential gaps follow ``repro.bench.arrival.PoissonArrivals``; they are
copied here, not imported, so that the program cannot move the yardstick.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Call:
    """One request as the traffic makes it; the harness times it."""
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due_s: Optional[float]      # open streams: seconds after window start


@dataclasses.dataclass
class Stream:
    name: str
    loop: str                   # "open" | "closed"
    calls: list                 # open: in due order; closed: in send order
    clients: int = 0            # closed streams
    slo: Optional[dict] = None  # {"ttft_s", "mean_itl_s"} when judged
    deadline_after_s: Optional[float] = None


def stratified(n: int) -> np.ndarray:
    """The quantile levels ``(i + 0.5) / n``."""
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    """``n`` stratified lognormal lengths, clipped, in ascending order."""
    z = np.array([_NORMAL.inv_cdf(q) for q in stratified(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, rate_per_s: float) -> np.ndarray:
    """``n`` stratified exponential inter-arrival gaps at ``rate_per_s``."""
    return -np.log1p(-stratified(n)) / rate_per_s


def _order(stream: str) -> np.random.Generator:
    """The stream's fixed order of lengths and gaps, the same for every
    seed."""
    return np.random.default_rng([*stream.encode()])


def _calls(n: int, spec: dict, vocab: int, order: np.random.Generator,
           rng: np.random.Generator) -> list:
    plens = order.permutation(lognormal_lengths(n, spec["prompt"]))
    outs = order.permutation(lognormal_lengths(n, spec["output"]))
    return [Call(rng.integers(0, vocab, int(p)).astype(np.int32), int(o),
                 None) for p, o in zip(plens, outs)]


def generate(traffic: dict, seconds: float, seed: int,
             vocab: int) -> list:
    """The streams of ``traffic`` for a window of ``seconds``."""
    out = []
    for spec in traffic["streams"]:
        name = spec["name"]
        order = _order(name)
        # the stream's name keeps two streams of one cell independent
        rng = np.random.default_rng([seed, *name.encode()])
        if spec["loop"] == "open":
            n = max(1, int(round(spec["rate_per_s"] * seconds)))
            calls = _calls(n, spec, vocab, order, rng)
            due = np.cumsum(order.permutation(
                exponential_gaps(n, spec["rate_per_s"])))
            for c, t in zip(calls, due):
                c.due_s = float(t)
            out.append(Stream(name, "open", calls, slo=spec.get("slo"),
                              deadline_after_s=spec.get("deadline_after_s")))
        elif spec["loop"] == "closed":
            calls = _calls(int(spec["calls"]), spec, vocab, order, rng)
            out.append(Stream(name, "closed", calls,
                              clients=int(spec["clients"]),
                              slo=spec.get("slo")))
        else:
            raise ValueError(f"stream {name!r}: loop must be 'open' or "
                             f"'closed', not {spec['loop']!r}")
    return out


def prefill_widths(streams: list, chunk: int) -> list:
    """Every prefill dispatch width a chunked prefill of these prompts uses:
    whole chunks of ``chunk`` tokens and each prompt's remainder."""
    widths = set()
    for s in streams:
        for c in s.calls:
            p = len(c.prompt)
            if p >= chunk:
                widths.add(chunk)
            if p % chunk:
                widths.add(p % chunk)
    return sorted(widths)
