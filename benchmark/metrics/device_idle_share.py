"""1 - the union of the device's operation intervals over the traced
window."""
from benchmark.harness.readers import capture


def read(run):
    cap = capture(run)
    if cap is None or cap["reduced"]["window_s"] <= 0:
        return None
    red = cap["reduced"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
