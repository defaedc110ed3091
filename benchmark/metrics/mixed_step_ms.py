"""One mixed step on the device: the self time of the operations under
``mixed_step`` (``forward_mixed`` and its two samplings) in the first
capture's whole runs, over the whole runs of the programs that hold one
(``harness/scopes.py``)."""
from benchmark.harness.scopes import per_mixed_run_ms


def read(run):
    return per_mixed_run_ms(run)
