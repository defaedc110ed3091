"""Distinct experts a routed layer touched in one run of it (a decode
step's, or a mixed step's with its prefill slices): the program's count
(``moe_touched`` over ``moe_layer_runs`` on ``engine.commit``, summed
over the first capture's commits). The experts touched are the expert
matrices a step must read."""
from benchmark.harness.commits import routed_runs


def read(run):
    got = routed_runs(run)
    runs = sum(c["moe_layer_runs"] for c in got)
    return sum(c["moe_touched"] for c in got) / runs if runs else None
