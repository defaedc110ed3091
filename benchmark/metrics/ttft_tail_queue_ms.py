"""Of the tail's mean TTFT (``harness/waits.py``: the window's requests at
or above its 90th percentile of TTFT, the MEAN over them), the leg from
``enqueued`` until a worker took the message (``scheduled``, else
``dispatched``): the queue plane and the worker's tick. ``None`` where
no request of the tail has every mark (a request missing one is left out
of all seven legs)."""
from benchmark.harness.waits import leg

read = leg("queue")
