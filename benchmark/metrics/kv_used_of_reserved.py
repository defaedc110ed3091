"""KV in use against KV reserved: tokens written in the pages that live
sequences hold (``tokens_live`` on ``engine.dispatch``: sequences that
own a row or wait with pages) over those pages' capacity
(``pages_live`` x the page size), summed over the first capture's
dispatches. What is missing from 100 is page tails and the pages
allocated ahead of a chunk's budget."""
from benchmark.harness.spans import of_run


def read(run):
    red = of_run(run)
    if red is None:
        return None
    page = int(run["config"]["server"]["executor"]["page_size"])
    got = [d for d in red["dispatches"] if d.get("pages_live")]
    room = sum(d["pages_live"] for d in got) * page
    return 100.0 * sum(d["tokens_live"] for d in got) / room if room else None
