"""Prefill attention deep in a context: the self time under
``mixed_step/slices/.../attn_full`` — the full-attention layers' calls
over a mixed step's prompt slices, each slice's queries against every
key its sequence has cached so far (34 k at the end of the longest
document) — over the whole runs of the programs that hold a mixed step
(as ``slices_dense_ms``). The decode rows' attention in the same step is
left out. In a closed loop no end-to-end metric reads it: a prompt's
first token is not timed there, and the gap between a row's tokens
carries it only as a share of a chunk. By the scope; a program without
it gives nothing."""
from benchmark.harness.scopes import DECODE_ROWS, per_mixed_run_ms


def read(run):
    return per_mixed_run_ms(run, ("attn_full",), without=DECODE_ROWS) or None
