"""Device idle time of the first capture under the engine thread's
``engine.ingest``, ``engine.admit``, ``engine.prefill_advance`` and
``engine.resolve``, and under ``engine.step`` itself between its
phases, as a share of the traced window (``harness/spans.py``)."""
from benchmark.harness.spans import idle_share


def read(run):
    return idle_share(run, "schedule")
