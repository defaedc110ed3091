"""Key chunks the window start skipped, of those the decoding rows hold
a position in: ``window_chunks_skipped`` over ``window_chunks`` +
``window_chunks_skipped`` on ``engine.dispatch`` (the host's count by
the decode kernel's own schedule, the executor's ``window_chunks``: one
sliding layer's call at a chunk's first step),
summed over the first capture's chunks. The share of a sliding layer's
visits that a kernel without a window start would have made for
nothing. A program without the counters gives nothing."""
from benchmark.harness.spans import chunks


def read(run):
    got = [d for d in chunks(run) if "window_chunks" in d]
    skipped = sum(d["window_chunks_skipped"] for d in got)
    held = skipped + sum(d["window_chunks"] for d in got)
    return 100.0 * skipped / held if held else None
