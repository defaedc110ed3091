"""Rows a decode step kept busy, counted at the dispatch: the sum of
the row budgets (``row_steps`` on ``engine.dispatch``, rows x steps
where every row runs the chunk out) over the sum of the device steps
dispatched (``steps``), over the first capture. ``batch_rows_mean``
reads the same from the device side (tokens delivered over decode
attention calls / layers)."""
from benchmark.harness.spans import chunks


def read(run):
    got = chunks(run)
    steps = sum(d["steps"] for d in got)
    return sum(d.get("row_steps", 0) for d in got) / steps if steps else None
