"""Device idle time of the first capture under ``engine.wait`` spans
that opened with nothing pending, no row active and no chunk in flight,
as a share of the traced window (``harness/waits.py``): the traffic's
idle, which no program can have back. A part of ``idle_unnamed_share``.
``None`` for a program that opens no ``engine.wait``; 0 where it does
and its loop never slept inside the capture."""
from benchmark.harness.waits import EMPTY, wait_share

read = wait_share(EMPTY)
