"""The family's decode attention kernel (its ``shapes.DECODE_ATTN``):
the least time its calls of one step could take (by the family's
``decode_attn_bytes`` / ``_flops`` at the chip's peaks: for the batch's
cached K/V read once it is memory-bound) over the time they took."""
from benchmark.harness.readers import (capture, decode_steps, family_shapes,
                                       itemsizes, least_time, mean_load,
                                       ops_time)


def read(run):
    cap = capture(run)
    if cap is None or not decode_steps(run, cap):
        return None
    shapes = family_shapes(run)
    t = ops_time(cap, shapes.DECODE_ATTN) / decode_steps(run, cap)
    load = mean_load(cap)
    if t <= 0 or load is None:
        return None
    rows, ctx = load
    _w, kv = itemsizes(run)
    model = run["config"]["model"]
    least = least_time(run, shapes.decode_attn_bytes(model, kv, rows, ctx),
                       shapes.decode_attn_flops(model, rows, ctx), False)
    return 100.0 * least / t
