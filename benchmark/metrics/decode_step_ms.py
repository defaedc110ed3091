"""Device time of the program runs that decode (decode chunks and
mixed chunks, recognised by the kernels inside them) in the capture, over
the decode steps the device ran there (decode attention calls / the
family's calls a step)."""
from benchmark.harness.readers import decode_step_ms as read  # noqa: F401
