"""How far the hyper-connection sites' ``H_res`` stand from doubly
stochastic: the worst ``|row sum - 1|`` over a step's sites and live
rows, in millionths, as the program counted it (``hc_row_sum_err`` on
``engine.commit``: a chunk's steps summed), the mean over the steps of
the first capture's commits (``moe_layer_runs`` over the routed layers
held says how many ran). The Sinkhorn projection's last step normalises
the columns, so what its twenty steps left undone shows in the rows: a
site that stops being doubly stochastic is a wrong model that still
emits tokens, and no clock sees it. A program that does not count it
(every family of one stream; a parent commit) gives nothing."""
from benchmark.harness.commits import routed_runs
from benchmark.harness.readers import family_shapes


def read(run):
    got = [c for c in routed_runs(run) if "hc_row_sum_err" in c]
    if not got:
        return None
    model = run["config"]["model"]
    held = model["num_hidden_layers"] - model.get(
        "dense_layers_held", model["first_k_dense_replace"])
    steps = sum(c["moe_layer_runs"] for c in got) / max(held, 1)
    if not steps or not hasattr(family_shapes(run), "hc_mix_bytes"):
        return None
    return sum(c["hc_row_sum_err"] for c in got) / steps
