"""Of the tail's mean TTFT (``harness/waits.py``: the window's requests at
or above its 90th percentile of TTFT, the MEAN over them), the leg from
``prefill_last_dispatched`` until its first token was committed
(``first_token``): the final chunk's run, the chunk in flight before it,
the fetch and the commit. ``None`` where no request of the tail has
every mark (a request missing one is left out of all seven legs)."""
from benchmark.harness.waits import leg

read = leg("reconcile")
