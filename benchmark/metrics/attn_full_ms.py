"""Device time of a plain decode step under the program's ``attn_full``
scope: the full-attention layers' attention calls (every cached key of
every row, out of the page pool), all such layers of one step. By the
scope. A program without the scope gives nothing."""
from benchmark.harness.scopes import per_plain_step_ms


def read(run):
    return per_plain_step_ms(run, ("attn_full",)) or None
