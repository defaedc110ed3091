"""Process start to window open: JAX start, weights, the logits
check, compilation or cache loads, warm-up, and the ramp."""


def read(run):
    return run["setup_s"]
