"""Admissions of the window that found a cached prefix and were DECLINED
it — a family with row state that pages alone cannot rebuild, or one
served without tail slots — over the window's admissions that looked
one up: the program's counters (``prefix_cache.declined`` against
``admission_hits + admission_misses`` in the marks at the window's two
edges). 0 where every hit is adopted. A program without the counter (a
family whose pages are its whole cache; the parent of the PR that added
it) gives nothing."""


def read(run):
    a = (run.get("opened") or {}).get("prefix_cache") or {}
    b = (run.get("closed") or {}).get("prefix_cache") or {}
    if "declined" not in a or "declined" not in b:
        return None
    looked = sum(b.get(k, 0) - a.get(k, 0)
                 for k in ("admission_hits", "admission_misses"))
    return 100.0 * (b["declined"] - a["declined"]) / looked if looked else None
