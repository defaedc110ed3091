"""Of the first capture's ``engine.fill`` spans (one a step in which
the fill rule let a chunk be dispatched from the newest chunk's carried
state), the share whose ``stopped`` is not ``depth``: the pipeline was
left short of its depth, for the reason the engine gave —
``free_slot`` (a row is free and somebody could take it),
``urgent_pending`` (an arrival not ingested, or one that may preempt),
``cancelled``, ``geometry`` (the batch changed under the carry),
``pages`` (none to be had without a victim), ``row_ended``,
``nothing_to_decode``, ``tenancy`` (docs/performance.md "The fill
rule"). ``None`` where the capture holds no fill."""
from benchmark.harness.waits import fills


def read(run):
    stopped = [f["stopped"] for f in fills(run)]
    if not stopped:
        return None
    return 100.0 * sum(1 for s in stopped if s != "depth") / len(stopped)
