"""The family's prefill attention kernel (its ``shapes.PREFILL_ATTN``):
the least time the prefill work inside the capture could take (by the
family's ``prefill_attn_flops`` / ``_bytes``: its QK^T and PV at peak
FLOP/s, or its K/V, q and output at peak bytes/s, whichever is longer)
over the time the kernel's calls took."""
from benchmark.harness.readers import (capture, family_shapes, itemsizes,
                                       least_time, ops_time, prefill_work)


def read(run):
    cap = capture(run)
    if cap is None:
        return None
    shapes = family_shapes(run)
    t = ops_time(cap, shapes.PREFILL_ATTN)
    new, pairs, ctx = prefill_work(run, cap)
    if t <= 0 or new <= 0:
        return None
    model = run["config"]["model"]
    _w, kv = itemsizes(run)
    least = least_time(run, shapes.prefill_attn_bytes(model, kv, new, ctx),
                       shapes.prefill_attn_flops(model, pairs), False)
    return 100.0 * least / t
