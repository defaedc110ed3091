"""The logits check's share of set-up (``setup_stages_s.correctness``
of the child's ``ready`` message): tracing or loading the two check
programs, their XLA compilation or cache load, the runs, and the
float32 reference. With ``setup_engine_s`` it says whether a
``setup_s`` that moved was the program (both stay) or the compile cache
(one of them jumps by a compilation)."""


def read(run):
    return ((run.get("ready") or {}).get("stages_s") or {}).get("correctness")
