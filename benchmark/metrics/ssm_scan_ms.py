"""The chunked scan of a mixed step's prompt slices: the self time under
``mixed_step/.../{ssm_conv, ssm_scan}`` (the convolution over the
slices and the recurrence a chunk at a time, every Mamba layer; the
decode rows' update in the same step is left out) over the whole runs of
the programs that hold a mixed step (as ``slices_dense_ms``). A program
without those scopes gives nothing (``harness/scopes.py``)."""
from benchmark.harness.scopes import DECODE_ROWS, per_mixed_run_ms

SSM_SLICES = ("ssm_conv", "ssm_scan")


def read(run):
    return per_mixed_run_ms(run, SSM_SLICES, without=DECODE_ROWS) or None
