"""Batch rows a decode step kept busy: tokens delivered while the first
capture was held (the tap's count) over the decode steps the device ran
in it (decode attention calls / the family's calls a step).
``engine/stats.decode_steps`` counts dispatches, not steps, so it is not
used."""
from benchmark.harness.readers import capture, decode_steps


def read(run):
    cap = capture(run)
    if cap is None:
        return None
    steps = decode_steps(run, cap)
    try:
        tokens = cap["after"]["tokens"] - cap["before"]["tokens"]
    except (KeyError, TypeError):
        return None
    return tokens / steps if steps else None
