"""One plain decode step on the device: the self time of the operations
under ``decode_loop`` (the ``lax.while_loop`` of the chunk programs, its
own overhead included) in the first capture's whole runs, over the
decode steps run there (the family's decode attention calls under
``decode_loop`` / its calls a step). ``decode_step_ms`` divides mixed
steps' time by decode steps too; this does not (``harness/scopes.py``)."""
from benchmark.harness.scopes import per_plain_step_ms


def read(run):
    return per_plain_step_ms(run)
