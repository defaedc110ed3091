"""Device time of a plain decode step under the program's
``attn_window`` scope: the sliding-window layers' attention calls (the
current token's write and the read of the row's last ``sliding_window``
keys out of its slab), all such layers of one step. By the scope, so it
reads the same work whatever implements it. A program without the scope
(another family; the parent of the PR that added it) gives nothing."""
from benchmark.harness.scopes import per_plain_step_ms


def read(run):
    return per_plain_step_ms(run, ("attn_window",)) or None
