"""What padding inflates: the self time of a mixed step's dense products
over the slice rows (``mixed_step/slices/{qkv, attn_out, mlp,
moe_experts, head}``, and the same modules directly under ``mixed_step``
where a family runs its slice rows and its decode rows through one
product, the S x T slice rows being all but B of them), over the whole
runs of the programs that hold a mixed step. The decode rows' own
products (``mixed_step/decode_rows/...``) are left out
(``harness/scopes.py``)."""
from benchmark.harness.scopes import (DECODE_ROWS, SLICES_DENSE,
                                      per_mixed_run_ms)


def read(run):
    return per_mixed_run_ms(run, SLICES_DENSE, without=DECODE_ROWS)
