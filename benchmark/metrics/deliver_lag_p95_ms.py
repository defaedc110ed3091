"""Recorder: the last leg of TTFT — a request's first token committed
on the engine thread (``first_token``) until the completion pool hands
it to the consumer (``first_token_out``, stamped just before the first
``on_token`` call)."""
from benchmark.harness.readers import stage_tail

read = stage_tail(("first_token",), ("first_token_out",), 95)
