"""The least time a decode step could take (by the family's
``decode_step_bytes`` / ``_flops``: every matrix and the batch's cached
K/V read once at the chip's peak bytes/s, or its operations at peak,
whichever is longer: the bytes, on this chip) over the time it took."""
from benchmark.harness.readers import (capture, decode_step_ms,
                                       family_shapes, itemsizes, least_time,
                                       mean_load)


def read(run):
    cap, step = capture(run), decode_step_ms(run)
    load = mean_load(cap) if cap else None
    if not step or load is None:
        return None
    rows, ctx = load
    w, kv = itemsizes(run)
    model = run["config"]["model"]
    shapes = family_shapes(run)
    least = least_time(run,
                       shapes.decode_step_bytes(model, w, kv, rows, ctx),
                       shapes.decode_step_flops(model, rows, ctx), w == 1)
    return 100.0 * least / (step / 1e3)
