"""Counts the program leaves on its ``engine.commit`` spans, read from
a run's FIRST capture (``spans.neutral_of``: the engine thread's
``engine.*`` events with their arguments). A program that leaves none
(a dense model; the parent of the PR that added them) gives an empty
list, and the readers built on this return nothing."""

from __future__ import annotations

import os
from typing import Any, Dict, List

from benchmark.harness import spans

COMMIT = "engine.commit"


def commit_counts(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The arguments of every ``engine.commit`` span of the first
    capture that carries any, in time order. Kept on the run."""
    if "_commits" not in run:
        caps = run.get("captures") or []
        trace_dir = caps[0].get("dir") if caps else None
        trace = (spans.neutral_of(trace_dir)
                 if trace_dir and os.path.isdir(trace_dir) else None)
        line = spans.engine_line(trace) if trace else None
        run["_commits"] = [e[3] for e in (line["events"] if line else [])
                           if e[0] == COMMIT and len(e) > 3]
    return run["_commits"]


def routed_runs(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The commits of chunks that ran routed layers: ``moe_layer_runs``
    (steps x routed layers), ``moe_touched`` (distinct experts, summed
    over those runs), ``moe_pairs`` ((token, expert) pairs multiplied)
    and ``moe_load`` (tokens an expert: ``n<count>_<count>_...``)."""
    return [c for c in commit_counts(run) if c.get("moe_layer_runs")]
