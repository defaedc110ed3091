"""The dense weight stream of one plain decode step: the self time under
``decode_loop/.../{qkv, attn_out, mlp, head}`` in the first capture's
whole runs over the decode steps run there (as
``plain_decode_step_ms``). Attention, the cache write, sampling and a
routed layer's experts are left out (``harness/scopes.py``)."""
from benchmark.harness.scopes import DECODE_DENSE, per_plain_step_ms


def read(run):
    return per_plain_step_ms(run, DECODE_DENSE)
