"""Recorder: requests that lost their row or their pages at least once
(the ``preempted`` mark) over the requests that left a timeline.
``None`` from a program that stamps neither of the marks this metric's
PR added (no request shows ``first_token_out``)."""


def read(run):
    seen = [r.get("stages") or {} for r in run["requests"]]
    seen = [st for st in seen if st]
    if not any("first_token_out" in st for st in seen):
        return None
    return 100.0 * sum(1 for st in seen if "preempted" in st) / len(seen)
