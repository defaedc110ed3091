"""Recorder: a worker took the message until the engine gave it a
batch row (pages found, row free)."""
from benchmark.harness.readers import stage_tail

read = stage_tail(("scheduled", "dispatched"), ("admitted",), 90)
