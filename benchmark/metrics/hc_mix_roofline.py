"""The hyper-connection sites of a MIXED step against the chip's peaks:
the least time the counted work could take — the family's
``shapes.hc_mix_bytes`` / ``_flops`` of the rows the program counted on
its dispatches (``hc_rows_live`` on ``engine.dispatch``: the step's
decode rows and the live row tiles of its prompt slices; the mean over
the capture's dispatches that carry it), bytes at the HBM peak or
operations at the bf16 peak, the longer — over the self time under
``mixed_step/.../hc_mix`` a whole run of the programs that hold a mixed
step. By the SCOPE, so it reads the same work whatever implements it
(XLA's fusions today, a kernel later), and the bytes are the LEAST a
site can move (the float32 streams read once and written once a site,
Phi once): neither can read over 100 %. Both sides are per run, so the
capture's edges do not enter. A family whose ``shapes`` counts no such
work, a program without the scope or the count (every parent of the PR
that brought this), gives nothing."""
from benchmark.harness.readers import family_shapes, least_time
from benchmark.harness.scopes import per_mixed_run_ms
from benchmark.harness.spans import chunks


def read(run):
    mix_ms = per_mixed_run_ms(run, ("hc_mix",))
    rows = [d["hc_rows_live"] for d in chunks(run)
            if d.get("hc_rows_live", 0) > 0]
    if not mix_ms or not rows:
        return None
    shapes = family_shapes(run)
    flops = getattr(shapes, "hc_mix_flops", None)
    nbytes = getattr(shapes, "hc_mix_bytes", None)
    if flops is None or nbytes is None:
        return None
    model = run["config"]["model"]
    per_run = sum(rows) / len(rows)
    least = least_time(run, nbytes(model, per_run), flops(model, per_run),
                       False)
    return 100.0 * least / (mix_ms / 1e3)
