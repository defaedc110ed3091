"""The latent prefill attention of a mixed step's prompt slices against
the chip's peaks: the least time the LIVE work could take — the family's
``shapes.latent_prefill_flops`` / ``_bytes`` of the keys the slices'
contexts hold and of their tokens' (query, visible key) pairs, as the
program counted them on its commits (``pf_live_keys`` /
``pf_live_pairs`` on ``engine.commit``: of one attention, by the slices'
own positions and lengths — not the key blocks the loop visits, which
are every slice's up to the longest context's), the mean over the
capture's commits that carry them, times the layers held — over the self
time under ``mixed_step/.../latent_prefill_attention`` a whole run of
the programs that hold a mixed step. By the SCOPE: XLA's loop over key
blocks today, a kernel later; the operations are the fewer of the
expanded and the absorbed form's, so neither can read over 100 %. A
program without the scope or the counts (a parent of the PR that brought
them) and a family whose ``shapes`` counts no such work give nothing."""
from benchmark.harness.commits import commit_counts
from benchmark.harness.readers import family_shapes, itemsizes, least_time
from benchmark.harness.scopes import per_mixed_run_ms
from benchmark.harness.spans import chunks


def read(run):
    attn_ms = per_mixed_run_ms(run, ("latent_prefill_attention",))
    got = [c for c in commit_counts(run) if c.get("pf_live_keys")]
    if not attn_ms or not got:
        return None
    shapes = family_shapes(run)
    flops = getattr(shapes, "latent_prefill_flops", None)
    nbytes = getattr(shapes, "latent_prefill_bytes", None)
    if flops is None or nbytes is None:
        return None
    model = run["config"]["model"]
    keys = sum(c["pf_live_keys"] for c in got) / len(got)
    pairs = sum(c["pf_live_pairs"] for c in got) / len(got)
    mixed = [d["prefill_tokens"] for d in chunks(run)
             if d.get("prefill_tokens", 0) > 0]
    queries = sum(mixed) / len(mixed) if mixed else 0.0
    layers = shapes.attn_calls_per_step(model)
    _w, kv = itemsizes(run)
    least = least_time(run, layers * nbytes(model, kv, keys, queries),
                       layers * flops(model, keys, queries, pairs), False)
    return 100.0 * least / (attn_ms / 1e3)
