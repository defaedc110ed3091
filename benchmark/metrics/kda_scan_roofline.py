"""The delta-rule layers' chunked scan of a mixed step's prompt slices
against the chip's peaks: the least time the counted work could take —
the family's ``shapes.kda_scan_flops`` / ``_bytes`` of the LIVE 64-token
chunks the program counted on its dispatches (``scan_chunks_live`` on
``engine.dispatch``: one layer's, by the slices' lengths; the mean over
the capture's dispatches that carry it, times the family's recurrent
layers), multiply-adds x 2 at the bf16 peak or bytes at the HBM peak,
the longer — over the self time under ``mixed_step/.../ssm_scan`` a
whole run of the programs that hold a mixed step (the decode rows'
update in the same step is left out, as ``ssm_scan_ms`` does; the
convolution's ``ssm_conv`` is not the scan's). By the SCOPE, so it reads
the same work whatever implements it: the kernel, or XLA's scan — and a
float32 kernel held to the bf16 peak cannot read over 100 %. Both sides
are per run, so the capture's edges do not enter. A family whose
``shapes`` counts no such work, a program without the scope or the
count (every parent of the PR that brought this), gives nothing."""
from benchmark.harness.readers import family_shapes, least_time
from benchmark.harness.scopes import DECODE_ROWS, per_mixed_run_ms
from benchmark.harness.spans import chunks


def read(run):
    scan_ms = per_mixed_run_ms(run, ("ssm_scan",), without=DECODE_ROWS)
    live = [d["scan_chunks_live"] for d in chunks(run)
            if d.get("scan_chunks", 0) > 0]
    if not scan_ms or not live:
        return None
    shapes = family_shapes(run)
    flops = getattr(shapes, "kda_scan_flops", None)
    nbytes = getattr(shapes, "kda_scan_bytes", None)
    if flops is None or nbytes is None:
        return None
    model = run["config"]["model"]
    per_run = sum(live) / len(live) * shapes.layer_kinds(model)[0]
    least = least_time(run, nbytes(model, per_run), flops(model, per_run),
                       False)
    return 100.0 * least / (scan_ms / 1e3)
