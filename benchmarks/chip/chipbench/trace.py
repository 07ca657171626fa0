"""The reduction from a profiler trace of the measured window to device
metrics: busy and idle time, the device time of each kind of step program,
each attention kernel's time, and the ``breakdown`` of the result line.

A trace is first cut down to plain lists (``extract``), the form a test
keeps on disk: device module executions, device operations and host spans,
each ``[name, start_ns, end_ns]`` on one clock.

The engine's jitted step programs are all lambdas, so every one of them is
named ``jit(<lambda>)`` in the trace. A program is told apart by the Pallas
kernel it holds: one that runs ``paged_prefill_attention`` is a prefill
step, one that runs ``paged_decode_attention`` a decode step, and any other
(the cache-slot reset, the argmax) is ``other``.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import pathlib
import re
import shutil

#: kernel name (the prefix of its operation's name) -> the step it marks
KERNELS = {"paged_prefill_attention": "prefill",
           "paged_decode_attention": "decode"}
#: host spans the harness writes around its own phases
WINDOW, WAIT = "bench.window", "bench.wait"
TOP = 10


@dataclasses.dataclass
class Reduced:
    window_s: float        # the traced window
    busy_s: float          # union of device operations inside it
    pending_s: float       # the window less the harness's waits for arrivals
    idle_pending_s: float  # pending time with no device operation running
    program_s: dict        # "prefill" | "decode" | "other" -> device seconds
    kernel_s: dict         # kernel -> device seconds of its operations
    breakdown: dict        # {"device_ops": [...], "idle_gaps": [...]}


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a, b) -> list:
    """Union intervals ``a`` less union intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _op(name: str) -> str:
    """An operation's instruction name: the chip's trace names it by its
    whole HLO text, ``%fusion.12 = (...) fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _kernel_of(name: str):
    name = _op(name)
    for k in KERNELS:
        if name.startswith(k):
            return k
    return None


def _base(name: str) -> str:
    """An operation's name without its instruction number."""
    return re.sub(r"\.\d+$", "", _op(name))


def reduce(plain: dict) -> Reduced:
    """Device metrics of the traced window from ``extract``'s lists."""
    wins = [(s, e) for n, s, e in plain["host"] if n == WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    lo, hi = wins[0]
    ops = sorted((s, e, n) for n, s, e in plain["ops"] if e > lo and s < hi)
    busy = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
    waits = _union(_clip([(s, e) for n, s, e in plain["host"] if n == WAIT],
                         lo, hi))
    pending = _subtract([[lo, hi]], waits)
    idle = _subtract(pending, busy)

    starts = [s for s, _, _ in ops]
    program_s: dict = {}
    op_time: dict = {}
    for name, s, e in plain["modules"]:
        if e <= lo or s >= hi:
            continue
        i, j = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        inside = ops[i:j]
        kinds = {KERNELS[k] for k in map(_kernel_of, (n for _, _, n in inside))
                 if k}
        kind = kinds.pop() if len(kinds) == 1 else "other"
        program_s[kind] = program_s.get(kind, 0) + (min(e, hi) - max(s, lo))
        for os_, oe, on in inside:
            key = f"{kind}:{_base(on)}"
            op_time[key] = op_time.get(key, 0) + (min(oe, hi) - max(os_, lo))
    kernel_s: dict = {}
    for s, e, n in ops:
        k = _kernel_of(n)
        if k:
            kernel_s[k] = kernel_s.get(k, 0) + (min(e, hi) - max(s, lo))

    # each idle stretch is charged to the innermost span of the harness's
    # thread open at its middle (one thread's spans nest)
    host = sorted((s, -e, n) for n, s, e in plain["host"]
                  if n != WINDOW and e > lo and s < hi)
    gap_time: dict = {}
    stack: list = []
    i = 0
    for s, e in sorted(idle):
        mid = (s + e) // 2
        while i < len(host) and host[i][0] <= mid:
            hs, neg_end, name = host[i]
            while stack and stack[-1][0] <= hs:
                stack.pop()
            stack.append((-neg_end, name))
            i += 1
        while stack and stack[-1][0] <= mid:
            stack.pop()
        who = stack[-1][1] if stack else "(no span)"
        gap_time[who] = gap_time.get(who, 0) + (e - s)

    def top(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=_length(busy) * 1e-9,
                   pending_s=_length(pending) * 1e-9,
                   idle_pending_s=_length(idle) * 1e-9,
                   program_s={k: v * 1e-9 for k, v in program_s.items()},
                   kernel_s={k: v * 1e-9 for k, v in kernel_s.items()},
                   breakdown={"device_ops": top(op_time),
                              "idle_gaps": top(gap_time)})


# ------------------------------------------------------------ recording

def _events(line):
    for ev in line.events:
        yield [ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)]


def extract(profile) -> dict:
    """Plain lists from a ``jax.profiler.ProfileData``: the first device
    plane's module executions and operations, and the spans of the host
    thread that ran the harness."""
    plain = {"modules": [], "ops": [], "host": []}
    device = None
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") and device is None:
            device = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = list(_events(line))
                # the thread that ran the harness: what the host was doing
                if any(n.startswith("bench.") for n, _, _ in events):
                    plain["host"].extend(events)
    if device is not None:
        for line in device.lines:
            if line.name == "XLA Modules":
                plain["modules"].extend(_events(line))
            elif line.name == "XLA Ops":
                plain["ops"].extend(_events(line))
    return plain


class Recording:
    """A profiler trace of the measured window, written under ``path``
    (a temporary directory) and deleted once reduced."""

    def __init__(self, path):
        self.path = pathlib.Path(path)

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # no span per Python call
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.path), profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def load(self) -> dict:
        import jax
        found = sorted(self.path.rglob("*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no trace under {self.path}")
        return extract(jax.profiler.ProfileData.from_file(str(found[-1])))

    def reduce(self) -> Reduced:
        try:
            return reduce(self.load())
        finally:
            shutil.rmtree(self.path, ignore_errors=True)


def save(plain: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(plain, f)


def read(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
