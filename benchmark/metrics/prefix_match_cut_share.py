"""What the tails' grain costs: of the tokens the radix walk matched in
the window, the share given up because the deepest node WITH a tail lay
before the deepest matched block (``prefix_cache.match_cut_tokens`` over
``matched_tokens``, the marks at the window's two edges). Those tokens
are prefilled again. A program without the counters (a family that
needs no tail; the parent of the PR that added them) gives nothing."""


def read(run):
    a = (run.get("opened") or {}).get("prefix_cache") or {}
    b = (run.get("closed") or {}).get("prefix_cache") or {}
    if "matched_tokens" not in a or "matched_tokens" not in b:
        return None
    matched = b["matched_tokens"] - a["matched_tokens"]
    cut = b["match_cut_tokens"] - a["match_cut_tokens"]
    return 100.0 * cut / matched if matched else None
