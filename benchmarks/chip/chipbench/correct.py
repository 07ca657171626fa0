"""What decides ``correct``: the tokens the timed path served, against the
float32 reference run over the very sequences the program was fed.

``Dispatches`` wraps the engine's paged prefill and decode programs and
keeps, for every call, the device arrays it was given (read back only once
the window has closed) and which request held each slot. From those calls
alone it rebuilds, per request, the token at every cache position and the
position whose logits chose each served token. So the reference is given
what the engine dispatched, whatever that is (the engine's first decode
step, for one, feeds the prompt's last token again), and nothing is
assumed about it.

The number compared is the widest gap, over a sample of finished requests
drawn from the seed (the longest always in it), by which a served token's
reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

#: requests in the sample: the longest, and the rest drawn from the seed
SAMPLE = 4


@dataclasses.dataclass
class Call:
    kind: str                # "prefill" | "decode"
    tokens: object           # device (slots, c) int32
    lengths: np.ndarray      # the host lengths passed with the call
    mask: object             # device (slots,) bool
    valid: object            # device (slots,) int32, or None
    holders: list            # request id holding each slot, or None


class Dispatches:
    """Records every paged prefill and decode call an engine makes."""

    def __init__(self, engine):
        self.engine = engine
        self.calls: list = []
        prefill, decode = engine._jit_prefill_paged, engine._jit_decode_paged

        def prefill_rec(p, c, t, st, bt, act, val):
            self._note("prefill", t, act, val)
            return prefill(p, c, t, st, bt, act, val)

        def decode_rec(p, c, t, ln, bt, act):
            self._note("decode", t, act, None)
            return decode(p, c, t, ln, bt, act)

        engine._jit_prefill_paged = prefill_rec
        engine._jit_decode_paged = decode_rec

    def _note(self, kind, tokens, mask, valid):
        e = self.engine
        self.calls.append(Call(kind, tokens, e.lengths, mask, valid,
                               [None if r is None else r.request_id
                                for r in e.active]))

    def mark(self) -> int:
        return len(self.calls)

    def rows(self, lo: int = 0, hi: Optional[int] = None):
        """``(kind, request_id, start, tokens)`` for every live row of the
        calls ``lo:hi``, read back from the device."""
        for call in self.calls[lo:hi]:
            toks = np.asarray(call.tokens)
            mask = np.asarray(call.mask)
            valid = None if call.valid is None else np.asarray(call.valid)
            for s in np.flatnonzero(mask):
                n = toks.shape[1] if valid is None else int(valid[s])
                yield (call.kind, call.holders[s], int(call.lengths[s]),
                       toks[s, :n])


@dataclasses.dataclass
class Fed:
    """What one request was fed, rebuilt from the calls."""
    tokens: np.ndarray       # the token at every cache position
    out_positions: list      # position whose logits chose output k
    faults: list             # inconsistencies found while rebuilding


def rebuild(rows) -> dict:
    """``{request_id: Fed}`` from :meth:`Dispatches.rows`."""
    seqs: dict = {}
    outs: dict = {}
    faults: dict = {}
    for kind, rid, start, toks in rows:
        seq = seqs.setdefault(rid, {})
        for j, t in enumerate(toks):
            p = start + j
            if p in seq and seq[p] != int(t):
                faults.setdefault(rid, []).append(
                    f"position {p} fed {seq[p]} and then {int(t)}")
            seq[p] = int(t)
        if kind == "decode":
            outs.setdefault(rid, []).append(start)
    fed = {}
    for rid, seq in seqs.items():
        n = max(seq) + 1
        bad = list(faults.get(rid, []))
        if len(seq) != n:
            missing = sorted(set(range(n)) - set(seq))
            bad.append(f"positions never fed: {missing[:8]}")
        fed[rid] = Fed(np.array([seq.get(p, 0) for p in range(n)], np.int32),
                       outs.get(rid, []), bad)
    return fed


def sample(finished: list, seed: int, k: int = SAMPLE) -> list:
    """The longest finished request (prompt plus output) and ``k - 1`` more
    drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-(len(r.prompt) + len(r.tokens_out)),
                                            r.request_id))
    rest = order[1:]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(pick)]


def served_gaps(requests: list, logits: list) -> list:
    """Per request, how far each served token's reference logit lies below
    the reference's best at its position."""
    gaps = []
    for req, lg in zip(requests, logits):
        served = np.asarray(req.tokens_out, np.int64)
        gaps.append(lg.max(axis=-1) - lg[np.arange(len(served)), served])
    return gaps


def control_gaps(ref_logits: list, low_logits: list) -> list:
    """Per request, how far the token a lower precision puts first lies
    below the reference's best, at the same positions."""
    return [r.max(axis=-1) - r[np.arange(len(r)), lo.argmax(axis=-1)]
            for r, lo in zip(ref_logits, low_logits)]


def check_sample(requests: list, fed: dict) -> list:
    """Structural faults that make a request's comparison impossible."""
    faults = []
    for req in requests:
        f = fed.get(req.request_id)
        if f is None:
            faults.append(f"request {req.request_id}: never dispatched")
            continue
        faults.extend(f"request {req.request_id}: {x}" for x in f.faults)
        if len(f.out_positions) != len(req.tokens_out):
            faults.append(f"request {req.request_id}: {len(req.tokens_out)} "
                          f"tokens served from {len(f.out_positions)} decode "
                          "calls")
        if len(req.prompt) > len(f.tokens) or np.any(
                f.tokens[:len(req.prompt)] != np.asarray(req.prompt)):
            faults.append(f"request {req.request_id}: the prompt was not "
                          "fed as sent")
    return faults
