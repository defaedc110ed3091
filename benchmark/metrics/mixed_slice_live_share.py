"""The share of a mixed chunk's slice rows that hold a prompt token: the
sum of ``prefill_tokens`` (live) over the sum of ``slice_tokens``
(slices x slice width: what the program computes) on the first
capture's ``engine.dispatch`` spans of programs that decode and take
slices. The rest is padding every dense product still runs."""
from benchmark.harness.spans import chunks


def read(run):
    got = [d for d in chunks(run) if d.get("slice_tokens", 0) > 0]
    rows = sum(d["slice_tokens"] for d in got)
    return (100.0 * sum(d.get("prefill_tokens", 0) for d in got) / rows
            if rows else None)
