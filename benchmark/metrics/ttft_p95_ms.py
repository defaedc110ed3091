"""95th percentile of first token delivered minus the instant the
request was due, over every request due in the window."""
from benchmark.harness import stats
from benchmark.harness.readers import tail_of

read = tail_of(stats.ttft_ms, 95)
