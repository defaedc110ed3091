"""Recorder: admitted until the prompt's last slice ran (the first
token sampled)."""
from benchmark.harness.readers import stage_tail

read = stage_tail(("prefill_start", "admitted"),
                  ("prefill_done", "first_token"), 50)
