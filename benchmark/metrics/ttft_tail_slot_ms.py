"""Of the tail's mean TTFT (``harness/waits.py``: the window's requests at
or above its 90th percentile of TTFT, the MEAN over them), the leg from
``admitted`` until its first prompt slice was handed to a chunk
(``prefill_start``): a request that has its row and waits its turn in
the mixed token budget. ``None`` where no request of the tail has every
mark (a request missing one is left out of all seven legs)."""
from benchmark.harness.waits import leg

read = leg("slot")
