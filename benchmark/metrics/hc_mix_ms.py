"""Device time of a plain decode step under the program's ``hc_mix``
scope: the hyper-connection sites of every layer of one step — the
flattened norm, the product with Phi, the Sinkhorn projection of a
4 x 4 matrix a row (``hc_project``) and the two halves that apply them
(``hc_apply``: the sub-layer's input read out of the streams, its output
written back while the streams mix). Twelve sites a step over 32 rows: a
chain of small dependent operations, bound by latency and not by bytes.
By the scope. A program without the scope (every family but ``xing``; a
parent of the PR that brought it) gives nothing."""
from benchmark.harness.scopes import per_plain_step_ms


def read(run):
    return per_plain_step_ms(run, ("hc_mix",)) or None
