"""TPU compute ops: norms, rotary embeddings, attention (prefill + paged
decode), sampling. Pure-JAX reference implementations with Pallas TPU
kernels for the decode and prefill attention steps (``ops/pallas/``;
``ops/attention.py`` routes between them).

New scope — the reference delegates all model execution to external HTTP
endpoints (SURVEY.md §2.2); these ops are the in-tree TPU inference
backend mandated by BASELINE.json.
"""

from llmq_tpu.ops.norms import rms_norm  # noqa: F401
from llmq_tpu.ops.rope import apply_rope, rope_cos_sin  # noqa: F401
from llmq_tpu.ops.attention import (  # noqa: F401
    blockwise_prefill_attention,
    causal_prefill_attention,
    paged_decode_step,
    paged_decode_attention,
)
from llmq_tpu.ops.sampling import greedy, sample_token  # noqa: F401
from llmq_tpu.ops.ring_attention import (  # noqa: F401
    ring_attention,
    ring_attention_sharded,
)
