"""The share of the device's busy time under none of the program's
names: the self time of ``(unscoped)`` operations over the self time of
every operation of the first capture's whole runs. The guard that the
vocabulary still covers the step when a family or a program is added
(``harness/scopes.py``)."""
from benchmark.harness.scopes import UNSCOPED, share_of_busy


def read(run):
    return share_of_busy(
        run, lambda red: red["paths"].get(UNSCOPED, [0.0])[0])
