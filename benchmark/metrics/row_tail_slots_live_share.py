"""Tail slots that hold a tail — on a radix node, or taken and waiting
for theirs — over the slots the pool has, the mean of the marks at the
window's two edges (``prefix_cache.tail_slots_live`` over
``tail_slots``). At 100 the least recently used tail gives way to every
new one: a session whose tail went pays a cut match at its next turn
(``prefix_match_cut_share``). A program without the counters gives
nothing."""


def read(run):
    shares = []
    for mark in (run.get("opened"), run.get("closed")):
        pc = (mark or {}).get("prefix_cache") or {}
        if pc.get("tail_slots") and "tail_slots_live" in pc:
            shares.append(100.0 * pc["tail_slots_live"] / pc["tail_slots"])
    return sum(shares) / len(shares) if shares else None
