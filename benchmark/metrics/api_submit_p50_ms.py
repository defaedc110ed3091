"""Round trip of the POST that submits a message."""
from benchmark.harness.readers import tail_of


def _rtt(r):
    return None if r.get("rtt_s") is None else r["rtt_s"] * 1e3


read = tail_of(_rtt, 50, failed_sort_last=False)
