"""Device idle time of the first capture under every other
``engine.wait`` span — the loop slept while a request was pending, a
row active or a chunk in flight: wake-up latency, a missed wake — as a
share of the traced window (``harness/waits.py``). A part of
``idle_unnamed_share``; what is left of that share after this and
``idle_wait_empty_share`` is idle under no span of the engine thread:
another thread had the interpreter."""
from benchmark.harness.waits import STARVED, wait_share

read = wait_share(STARVED)
