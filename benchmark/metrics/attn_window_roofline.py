"""The sliding-window layers' decode attention against the chip's peak
bytes/s: the least time one step's calls could take (the family's
``shapes.attn_window_bytes`` of the PROGRAM'S window-bounded counter:
``window_tokens`` on ``engine.dispatch``, the decoding rows'
``min(context, window)`` summed, averaged over the first capture's
chunks — exact, where the accepted rooflines can only bound it from the
sum of the contexts) over the time under ``decode_loop/.../attn_window``
a plain decode step. By the scope, so it reads the same work whatever
implements it. A family whose ``shapes`` counts no such bytes, or a
program without the counter or the scope, gives nothing."""
from benchmark.harness.readers import family_shapes, itemsizes, least_time
from benchmark.harness.scopes import per_plain_step_ms
from benchmark.harness.spans import chunks


def read(run):
    got = [d for d in chunks(run) if "window_tokens" in d]
    step_ms = per_plain_step_ms(run, ("attn_window",))
    nbytes = getattr(family_shapes(run), "attn_window_bytes", None)
    if not got or not step_ms or nbytes is None:
        return None
    tokens = sum(d["window_tokens"] for d in got) / len(got)
    _w, kv = itemsizes(run)
    least = least_time(run, nbytes(run["config"]["model"], kv, tokens), 0.0,
                       False)
    return 100.0 * least / (step_ms / 1e3)
