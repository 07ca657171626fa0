"""Window arithmetic for the metrics read on the host clock.

Each request is timed from when it was due, on the harness's clock (seconds
since the window opened), with every token stamped when ``engine.step()``
returned it. A request still held by the engine at the close is
unfinished; one neither finished nor held (dropped, or cut short) is failed.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Timed:
    """One request as the harness saw it."""
    stream: str
    due_s: float
    max_new: int
    token_s: list = dataclasses.field(default_factory=list)
    failed: bool = False

    @property
    def done(self) -> bool:
        return not self.failed and len(self.token_s) >= self.max_new

    def gaps(self, until_s: float = float("inf")) -> list:
        """Gaps between consecutive tokens, the later one returned by
        ``until_s``."""
        t = self.token_s
        return [b - a for a, b in zip(t, t[1:]) if b <= until_s]


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile, linearly interpolated (numpy's default);
    None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
