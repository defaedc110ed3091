"""The decode attention kernel (``fused_decode_attention*``): the
least time its calls of one step could take (the batch's cached K/V read
once at peak bytes/s: it is memory-bound) over the time they took."""
from benchmark.harness import shapes
from benchmark.harness.readers import (capture, decode_steps, itemsizes,
                                       least_time, mean_load, ops_time)


def read(run):
    cap = capture(run)
    if cap is None or not decode_steps(run, cap):
        return None
    t = ops_time(cap, r"fused_decode_attention") / decode_steps(run, cap)
    load = mean_load(cap)
    if t <= 0 or load is None:
        return None
    _rows, ctx = load
    _w, kv = itemsizes(run)
    model = run["config"]["model"]
    least = least_time(run, shapes.decode_attn_bytes(model, kv, ctx),
                       shapes.decode_attn_flops(model, ctx), False)
    return 100.0 * least / t
