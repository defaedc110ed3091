"""Device time of a plain decode step under the program's ``kda_gates``
scope: a delta-rule (KDA) layer's gate products and their arithmetic —
the decay's ``W_f`` with its bias and ``A_log`` through the bounded
sigmoid, ``W_b`` and the output gate's ``W_g`` — every such layer of one
step. Neither ``qkv`` nor the state update, and as many matrix bytes as
``qkv`` again. By the scope. A program without the scope (every family
but ``ling_hybrid``; a parent of the PR that brought it) gives
nothing."""
from benchmark.harness.scopes import per_plain_step_ms


def read(run):
    return per_plain_step_ms(run, ("kda_gates",)) or None
