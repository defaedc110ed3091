"""Device time of a plain decode step under the program's ``moe_route``
scope: what stands between a routed layer's normed stream and its
grouped products, every routed layer of one step — the router (one
product and its scores; for a family whose router is a network, the
network and its carry from layer to layer), the choice, and the sort and
gather of the (token, expert) pairs. By the scope, so whatever a family
runs there. A program without the scope (a family that routes nothing; a
parent of the PR that brought the vocabulary) gives nothing."""
from benchmark.harness.scopes import per_plain_step_ms


def read(run):
    return per_plain_step_ms(run, ("moe_route",)) or None
