"""Prompt tokens served from cached KV (radix prefix or pinned
conversation) over all prompt tokens of the window's requests."""
from benchmark.harness.readers import context_split


def read(run):
    cached = total = 0
    for r in run["requests"]:
        split = context_split(r)
        if split is not None:
            cached += split[0]
            total += split[0] + split[1]
    return 100.0 * cached / total if total else None
