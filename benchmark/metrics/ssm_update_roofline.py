"""The decode state update against the chip's peak bytes/s: the least
time one step's update could take (by the family's
``shapes.ssm_update_bytes``: every live row's state of every Mamba layer
read once and written once — no operations worth counting beside them)
over the self time under ``decode_loop/.../ssm_update`` a decode step.
By the scope, so it reads the same work whatever implements it: the
in-place kernel, or XLA's fusion of the same. The rows are the mean
rows decoding while the capture was held (as the other rooflines). A
family whose ``shapes`` counts no such bytes, or a program without the
scope, gives nothing."""
from benchmark.harness.readers import (capture, family_shapes, least_time,
                                       mean_load)
from benchmark.harness.scopes import per_plain_step_ms


def read(run):
    cap = capture(run)
    load = mean_load(cap) if cap else None
    step_ms = per_plain_step_ms(run, ("ssm_update",))
    if load is None or not step_ms:
        return None
    nbytes = getattr(family_shapes(run), "ssm_update_bytes", None)
    if nbytes is None:
        return None
    least = least_time(run, nbytes(run["config"]["model"], load[0]), 0.0,
                       False)
    return 100.0 * least / (step_ms / 1e3)
