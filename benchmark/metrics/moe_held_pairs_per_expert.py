"""(token, expert) pairs a HELD expert received in one run of a routed
layer, on average: the program's counts on ``engine.commit``
(``moe_pairs`` over ``moe_layer_runs`` times the experts held, whose
number is the length of ``moe_load``), summed over the first capture's
commits. With a chip's share of the experts this is the grouped
product's batch an expert: two a decode step at the deployment's load;
below one, most of a step's expert reads serve a single token. Read
only where the program says which slots went elsewhere
(``moe_away_slots``): without a share every expert is held and
``moe_load_max_over_mean`` says the same."""
from benchmark.harness.commits import routed_runs


def read(run):
    got = [c for c in routed_runs(run) if "moe_away_slots" in c]
    runs = sum(c["moe_layer_runs"] for c in got)
    held = max((len([x for x in str(c.get("moe_load", ""))[1:].split("_")
                     if x]) for c in got), default=0)
    if not runs or not held:
        return None
    return sum(c["moe_pairs"] for c in got) / (runs * held)
