"""The state-space layers' share of one plain decode step: the self time
under ``decode_loop/.../{ssm_conv, ssm_update}`` (the convolution's
window step and the recurrent state's update, every Mamba layer) in the
first capture's whole runs over the decode steps run there (as
``plain_decode_step_ms``). By the scope, whatever implements the update
— the in-place kernel or XLA's fusion. A program without those scopes
gives nothing (``harness/scopes.py``)."""
from benchmark.harness.scopes import per_plain_step_ms

SSM_DECODE = ("ssm_conv", "ssm_update")


def read(run):
    return per_plain_step_ms(run, SSM_DECODE) or None
