"""The busiest expert's tokens over the mean expert's, over the first
capture: the program's per-expert counts (``moe_load`` on
``engine.commit`` as ``n<count>_<count>_...``, summed over layers,
steps and commits). 1 is a
perfect balance; the grouped product's longest group, and with it a
step's tail, grows with it."""
from benchmark.harness.commits import routed_runs


def read(run):
    total = None
    for c in routed_runs(run):
        load = [int(x) for x in str(c.get("moe_load", ""))[1:].split("_")
                if x]
        if load:
            total = load if total is None else [a + b for a, b in
                                                zip(total, load)]
    if not total or not sum(total):
        return None
    return max(total) / (sum(total) / len(total))
