"""Tokens inside windows over tokens reserved, in a sliding-window
layer's cache: the seated sequences' ``min(context, window)``
(``window_live`` on ``engine.dispatch``) over the tokens every batch
row's slab reserves (``window_reserved``), summed over the first
capture's chunks. What is missing from 100 is the slabs' slack (a
step's writes and a page), the rows whose context is shorter than the
window and the rows that are free: what a paged window budget would
later win. A program without the counters gives nothing."""
from benchmark.harness.spans import chunks


def read(run):
    got = [d for d in chunks(run) if d.get("window_reserved")]
    room = sum(d["window_reserved"] for d in got)
    return 100.0 * sum(d["window_live"] for d in got) / room if room else None
