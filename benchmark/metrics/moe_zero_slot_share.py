"""Of the slots a routed layer's tokens filled (``top-k`` a live
token), the share that chose a zero-compute expert: the program's
counts on ``engine.commit`` (``moe_zero_slots`` over ``moe_pairs +
moe_zero_slots + moe_away_slots``: the slots multiplied here, the ones
that cost nothing, and the ones whose expert another chip holds),
summed over the first capture's commits. What a token costs follows
it: a zero-compute slot adds the token itself and reads no matrix. A
program that does not count them (a family without such experts; a
parent commit) gives nothing."""
from benchmark.harness.commits import routed_runs


def read(run):
    got = [c for c in routed_runs(run) if "moe_zero_slots" in c]
    slots = sum(c["moe_pairs"] + c["moe_zero_slots"] + c["moe_away_slots"]
                for c in got)
    if not slots:
        return None
    return 100.0 * sum(c["moe_zero_slots"] for c in got) / slots
