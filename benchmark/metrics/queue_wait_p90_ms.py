"""Recorder: enqueued by the API until a worker took the message."""
from benchmark.harness.readers import stage_tail

read = stage_tail(("enqueued",), ("scheduled", "dispatched"), 90)
