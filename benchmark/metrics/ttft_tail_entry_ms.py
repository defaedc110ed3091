"""Of the tail's mean TTFT (``harness/waits.py``: the window's requests at
or above its 90th percentile of TTFT, the MEAN over them), the leg from
the request was due until the API enqueued it (``enqueued``): how late
the generator sent it, and the POST. ``None`` where no request of the
tail has every mark (a request missing one is left out of all seven
legs)."""
from benchmark.harness.waits import leg

read = leg("entry")
