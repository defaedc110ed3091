"""Device idle time of the first capture (Python tracer off) that fell
under the engine thread's ``engine.reconcile`` — its ``engine.fetch``
(blocked on the device) and ``engine.commit`` included — as a share of
the traced window. ``harness/spans.py``: by overlap, innermost span."""
from benchmark.harness.spans import idle_share


def read(run):
    return idle_share(run, "reconcile")
