"""The share of the device's busy time that mixed steps take: the self
time under ``mixed_step`` over the self time of every operation of the
first capture's whole runs (``harness/scopes.py``)."""
from benchmark.harness.scopes import MIXED, share_of_busy, under


def read(run):
    return share_of_busy(run, lambda red: under(red, MIXED))
