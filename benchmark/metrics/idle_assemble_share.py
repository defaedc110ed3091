"""Device idle time of the first capture under the engine thread's
``engine.assemble`` (host assembly of the next chunk,
``_budget_chunk_rows`` included), ``engine.fill`` (speculative
dispatches) and ``engine.dispatch`` (the executor call alone), as a
share of the traced window (``harness/spans.py``)."""
from benchmark.harness.spans import idle_share


def read(run):
    return idle_share(run, "assemble")
