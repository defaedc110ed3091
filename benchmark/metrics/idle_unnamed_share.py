"""Device idle time of the first capture under NO span of the engine
thread — its loop asleep, or another thread holding the interpreter —
as a share of the traced window (``harness/spans.py``). With the three
other ``idle_*`` shares it adds up to ``device_idle_share``."""
from benchmark.harness.spans import UNNAMED, idle_share


def read(run):
    return idle_share(run, UNNAMED)
