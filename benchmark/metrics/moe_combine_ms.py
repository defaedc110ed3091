"""Device time of a mixed step under the program's ``moe_combine``
scope: what a routed layer does with its experts' results, every routed
layer of one step — weighing each (token, expert) pair's row by its
gate and adding a token's rows up, with whatever moves the rows to
where they are added (a scatter-add of a block's rows into the tokens'
sum; or the block's rows written where they were sorted, the inverse of
the sort, and each token's gather of its own), the identity experts'
weight and the layer's counters — over the whole runs of the programs
that hold a mixed step (as ``mixed_step_ms``). The decode loop's
``moe_combine`` is left out: at a few rows a step it is small. By the
scope, so whatever a family runs there. A program without the scope (a
family that routes nothing) gives nothing."""
from benchmark.harness.scopes import per_mixed_run_ms


def read(run):
    return per_mixed_run_ms(run, ("moe_combine",)) or None
