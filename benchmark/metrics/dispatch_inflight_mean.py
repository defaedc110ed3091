"""Chunks in flight once a chunk is dispatched (``inflight`` on
``engine.dispatch``), its mean over the first capture's dispatches of
programs that decode: 1 is a pipeline that reconciles every chunk
before the next goes out, 2 the double buffer."""
from benchmark.harness.spans import chunks


def read(run):
    got = [d["inflight"] for d in chunks(run) if "inflight" in d]
    return sum(got) / len(got) if got else None
