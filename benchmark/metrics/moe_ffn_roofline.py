"""The routed layers' grouped product (the family's ``shapes.MOE_FFN``
kernel): the least time one run of a routed layer could take (by the
family's ``moe_ffn_bytes`` / ``_flops``: the matrices of the experts
the program COUNTED as touched, read once at the chip's peak bytes/s,
or the counted pairs' operations at peak, whichever is longer) over
the time the kernel's calls of one run took. Both sides are per run
(kernel time over its calls, two a run: gate-and-up, down; counts over
``moe_layer_runs``), so the capture's edges do not enter."""
from benchmark.harness.commits import routed_runs
from benchmark.harness.readers import (capture, family_shapes, itemsizes,
                                       least_time)

CALLS_PER_RUN = 2


def read(run):
    cap, got = capture(run), routed_runs(run)
    runs = sum(c["moe_layer_runs"] for c in got)
    if cap is None or not runs:
        return None
    shapes = family_shapes(run)
    pattern = getattr(shapes, "MOE_FFN", None)
    if not pattern:
        return None
    import re
    rx = re.compile(pattern)
    hits = [v for k, v in cap["reduced"]["ops"].items() if rx.search(k)]
    calls = sum(v[1] for v in hits)
    if not calls:
        return None
    t_run = sum(v[0] for v in hits) / (calls / CALLS_PER_RUN)
    w, _kv = itemsizes(run)
    model = run["config"]["model"]
    least = least_time(
        run, shapes.moe_ffn_bytes(model, w,
                                  sum(c["moe_touched"] for c in got) / runs),
        shapes.moe_ffn_flops(model, sum(c["moe_pairs"] for c in got) / runs),
        False)
    return 100.0 * least / t_run
