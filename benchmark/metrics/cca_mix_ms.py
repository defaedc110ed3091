"""Device time of a plain decode step under the program's ``cca_mix``
scope: compressed convolutional attention's MIX, every layer of one step
— the norm and the four compressed projections, the q-k mean, the two
2-tap convolutions over the row's tail, the two L2 norms with the key
temperature, the value shift and the tail's write-back. Neither the
rotation (``qkv``) nor the attention (``attn``). A chain of small
dependent operations a layer, bound by latency and not by bytes. By the
scope. A program without the scope (every family but ``zaya``; a parent
of the PR that brought it) gives nothing."""
from benchmark.harness.scopes import per_plain_step_ms


def read(run):
    return per_plain_step_ms(run, ("cca_mix",)) or None
