"""Median over streams of the mean gap between tokens."""
from benchmark.harness import stats
from benchmark.harness.readers import tail_of

read = tail_of(stats.tpot_ms, 50)
