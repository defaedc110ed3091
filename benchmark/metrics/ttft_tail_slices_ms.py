"""Of the tail's mean TTFT (``harness/waits.py``: the window's requests at
or above its 90th percentile of TTFT, the MEAN over them), the leg from
``prefill_start`` until its FINAL slice was handed to a chunk
(``prefill_last_dispatched``): the chunks its prompt took, times their
cadence. ``None`` where no request of the tail has every mark (a request
missing one is left out of all seven legs)."""
from benchmark.harness.waits import leg

read = leg("slices")
