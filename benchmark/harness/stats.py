"""Arithmetic on samples and on request records. Standard library
only; every reader under ``metrics/`` goes through these, so a tail is
computed one way everywhere."""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float,
               missing: int = 0) -> Optional[float]:
    """The ``q``-th percentile (nearest rank on the sorted samples) of
    ``values`` plus ``missing`` samples that sort last: a request that
    failed, was shed or never finished is missing in every tail. ``inf``
    when the rank falls among the missing; ``None`` with no sample."""
    n = len(values) + missing
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))
    if rank > len(values):
        return math.inf
    return sorted(values)[rank - 1]


def spread(values: Sequence[float]) -> Optional[float]:
    """The contract's spread: the distance between the first and the
    third quartile (``statistics.quantiles(values, n=4)``) as a share
    of the median."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def ttft_ms(req: Dict) -> Optional[float]:
    """First token delivered minus the instant the request was DUE."""
    if req.get("t_first") is None:
        return None
    return (req["t_first"] - req["due"]) * 1e3


def tpot_ms(req: Dict) -> Optional[float]:
    """Mean gap between tokens of one stream: (last - first) / (n - 1)
    over the stamps the request record carries (the whole stream in an
    open-loop cell, the stream's part inside the window in a closed
    loop). ``None`` under two tokens."""
    n = req.get("n_tokens") or 0
    if n < 2 or req.get("t_first") is None or req.get("t_last") is None:
        return None
    return (req["t_last"] - req["t_first"]) / (n - 1) * 1e3


def collect(reqs: Iterable[Dict], fn) -> List[float]:
    out = []
    for r in reqs:
        v = fn(r)
        if v is not None:
            out.append(v)
    return out
