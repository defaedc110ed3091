"""Of the tail's mean TTFT (``harness/waits.py``: the window's requests at
or above its 90th percentile of TTFT, the MEAN over them), the leg from
taken by a worker until the engine gave it a batch row (``admitted``):
the router, the submit, the inbox, pages and a free row. ``None`` where
no request of the tail has every mark (a request missing one is left out
of all seven legs)."""
from benchmark.harness.waits import leg

read = leg("admission")
