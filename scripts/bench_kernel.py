#!/usr/bin/env python
"""Micro-bench the attention kernels alone on the chip (dev tool; no
cell runs it): the fused decode kernel of the Llama block and, with
``--prefill``, its paged prefill kernel; or, for a configuration of the
family ``deepseek_v3``, the latent decode kernel and the latent write
(``ops/pallas/latent_decode.py``), each timed apart; or, for the family
``granitemoehybrid``, the Mamba-2 decode state update in place
(``ops/pallas/ssm_update.py``) beside XLA's fusion of the same
(``--lens ROWS``: that many of the batch's rows decode); or, for the
family ``mellum`` and with ``--routed`` for any family with routed
experts, one layer's routed experts over a mixed step's rows
(``--lens ROWS``: that many of the batch + token budget rows hold a
token, lying first): ``ops/moe.routed_ffn``'s plain form beside the
form that is told the count (``n_live``), at each ``--block`` of sorted
pairs and ``--tile`` of tokens, with the tiles ``ops/moe.gmm_tiling``
chose for the two grouped products; ``--tiling TM,TK,TN`` (repeatable) times the two products
ALONE (the megablox kernel over the sorted pairs of ROWS tokens, the
gate-up and the down product apart) at those tiles beside the rule's:
us a call, the (row tile, expert) visits, us a visit and the share of
the visited experts' bytes.

Heads, page size, block table width, batch, pool and the int8 kernel
come from a served configuration (``--model-file
benchmark/configs/<name>.json``); ``--lens`` gives the rows' contexts:

    chiprun -- python3 scripts/bench_kernel.py \\
        --model-file benchmark/configs/smollm2-1.7b-bf16.json \\
        --lens 31x300-1500 --lens 4x360 --lens 2x2000

One ``--lens`` is one occupancy: comma-separated ``N`` (one row of N
tokens), ``KxN`` (K such rows) or ``KxLO-HI`` (K rows spread evenly
over LO..HI); the rows left over are dead (``seq_len`` 0). Live rows
take the lowest slots, as the engine seats them (``--spread``: evenly
over the batch instead), in the order given (``--shuffle``: in a seeded
random order, as a served batch mixes its lengths). Prints, an occupancy: µs a call, what
``decode_work`` counts for it (steps, row-chunks computed, row-chunks
live) and the share of the K/V bytes' time at 819 GB/s (the benchmark's
``decode_attn_roofline`` for one call). ``--tree DIR`` imports
``llmq_tpu`` from another checkout (a parent commit unpacked beside
this one), ``--max-pages N`` widens or narrows the block table,
``--pages-per-chunk N`` overrides the plan's chunk, ``--out F`` writes
the numbers to F and each occupancy's attention output beside it.
``--order seat|sorted|gathered`` (repeatable) says how the rows reach
the kernel: as seated, in ``ops/attention.decode_order``'s order (the
kernel alone), or in that order through a gather of q, the new rows and
the output at every call, as serving has them
(``paged_decode_step(order=...)``); the fourth count printed is the
row-chunks that ran in full steps.
``--fused`` runs this bench at ANY file's heads and pages (granite's
four attention layers: ``--fused --layers 4``; Trinity's full layer:
``--fused --layers 1``, its sliding ones with ``--window 4096``;
``--kv-pages`` sizes the pool).

``--prefill LENGTH@START`` (repeatable, and then ``--lens`` may be left
out) times ONE slice of the mixed step through the prefill attention:
for a latent family ``models/latent.latent_prefill_attention`` (XLA,
a loop over the context's key blocks; ``--tree`` times a parent's), for
the family ``llama`` the kernel the file's pools take (bf16 or int8): a
slice of the mixed budget's width (``prefill_token_budget`` /
``max_slices``) holding LENGTH valid tokens from position START on.
Prints µs a call beside the plan's loop steps and the least time of
the work that is there: the products of the valid queries against
their visible context at 197 TFLOP/s, and the context's K/V (and
scales), q and the output at 819 GB/s. ``--pure`` times what served
an int8 pool before the kernel (``_dequant_window`` + the blockwise
softmax under XLA) beside it.
"""
import argparse
import json
import os
import sys
import time
from functools import partial

PEAK_BYTES_PER_S = 819e9  # TPU v5e (benchmark/harness/peaks.py)
PEAK_FLOPS_PER_S = 197e12  # bf16
REPS = 24                 # kernel calls fused into one jit program


def parse_lens(spec: str):
    lens = []
    for item in spec.split(","):
        count, _, span = item.rpartition("x")
        lo, _, hi = span.partition("-")
        k, lo, hi = int(count or 1), int(lo), int(hi or lo)
        lens += [lo + (hi - lo) * i // max(k - 1, 1) for i in range(k)]
    return lens


def occupancy(spec: str, B: int, ps: int, mp: int, P: int, rng, args):
    """(seq_lens (B,), block tables (B, mp), page of each row's last
    token) of one ``--lens`` occupancy over a pool of ``P`` pages."""
    import numpy as np
    lens = parse_lens(spec)
    assert len(lens) <= B and max(lens) <= mp * ps, spec
    seq = np.zeros(B, np.int32)
    at = (np.arange(len(lens)) * B // len(lens) if args.spread
          else np.arange(len(lens)))
    seq[at] = rng.permutation(lens) if args.shuffle else lens
    n_pages = -(-seq // ps)
    assert n_pages.sum() < P, "the pool is too small for these rows"
    ids = 1 + rng.permutation(P - 1)[:n_pages.sum()]
    bt = np.zeros((B, mp), np.int32)
    write_page = np.zeros(B, np.int32)
    for b, start in enumerate(np.cumsum(n_pages) - n_pages):
        bt[b, :n_pages[b]] = ids[start:start + n_pages[b]]
        if seq[b]:
            write_page[b] = bt[b, (seq[b] - 1) // ps]
    return len(lens), seq, bt, write_page


def random_pools(L: int, P: int, ps: int, GD: int, Hkv: int, q8: bool):
    """The Llama block's pools, (k, v) in bf16 or (k, v, k_scale,
    v_scale) in int8: one layer's random pages, repeated — a whole
    pool's random bits would not fit beside it."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.key(0), 2)
    if not q8:
        return tuple(jnp.tile(jax.random.normal(
            k, (1, P, ps, GD), jnp.bfloat16), (L, 1, 1, 1)) for k in keys)
    return tuple(jnp.tile(jax.random.randint(
        k, (1, P, ps, GD), -127, 128, jnp.int8), (L, 1, 1, 1))
        for k in keys) + tuple(
        jnp.full((L, P, Hkv, ps), 0.01, jnp.bfloat16) for _ in range(2))


def bench_latent(args, doc) -> None:
    """The latent decode kernel and the latent write at the served
    geometry: µs a call of each, and the share of the cached latents'
    time at 819 GB/s — against the published bytes (rank + rope values
    a token) and against the pool's (its lanes padded to 128)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.ops.pallas import latent_decode as ld

    ex, model = doc["server"]["executor"], doc["server"]["model"]
    H = doc["num_attention_heads"]
    # cache layers: one a layer, or a double layer's two attentions
    L = doc.get("num_hidden_layers") or 2 * doc["num_layers"]
    rank, dr = doc["kv_lora_rank"], doc["qk_rope_head_dim"]
    W = -(-(rank + dr) // 128) * 128
    reps = REPS
    if args.rehearse:
        L, reps = 2, 2
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from this host is no device "
                 "number (--rehearse runs the path in interpret mode)")
    B, ps, P = ex["max_batch_size"], ex["page_size"], ex["kv_pages"]
    mp = args.max_pages or model["max_seq_len"] // ps
    key = jax.random.key(0)
    pool = jnp.tile(jax.random.normal(key, (1, P, ps, W), jnp.bfloat16),
                    (L, 1, 1, 1))
    q = jax.random.normal(key, (B, H, W), jnp.bfloat16) * 0.05
    new = jnp.ones((B, W), jnp.bfloat16)

    @jax.jit
    def attend(pool, bt, seq_lens):
        outs = []
        for i in range(reps):
            # A query of its own a call: equal calls are one call to XLA.
            o = ld.latent_decode_attention_pallas(
                q * (1 + i / 64), pool, bt, seq_lens, jnp.int32(i % L),
                rank=rank, interpret=args.rehearse)
            outs.append(jnp.sum(o))
        return jnp.stack(outs), o

    @partial(jax.jit, donate_argnums=(0,))
    def write(pool, page_of, slot_of):
        for i in range(reps):
            pool = ld.latent_write_pallas(pool, new, page_of, slot_of,
                                          jnp.int32(i % L),
                                          interpret=args.rehearse)
        return pool

    print(f"{doc['name']}: B={B} H={H} W={W} rank={rank} ps={ps} "
          f"max_pages={mp} chunk_tokens="
          f"{ld.pages_per_chunk(ps, mp) * ps} "
          f"device={jax.devices()[0].device_kind}"
          f"{' REHEARSAL: times mean nothing' if args.rehearse else ''}",
          flush=True)
    rng = np.random.default_rng(0)
    n = 1 if args.rehearse else 10
    results = []
    for spec in args.lens:
        rows, seq, bt, write_page = occupancy(spec, B, ps, mp, P, rng, args)
        call = (jnp.asarray(bt), jnp.asarray(seq))
        outs, o = attend(pool, *call)
        finite = bool(np.isfinite(np.asarray(o)).all())
        t0 = time.perf_counter()
        for _ in range(n):
            outs, o = attend(pool, *call)
        jax.block_until_ready(outs)
        us = (time.perf_counter() - t0) / (n * reps) * 1e6
        wargs = (jnp.asarray(write_page), jnp.asarray((seq - 1) % ps))
        pool = write(pool, *wargs)
        t0 = time.perf_counter()
        for _ in range(n):
            pool = write(pool, *wargs)
        jax.block_until_ready(pool)
        write_us = (time.perf_counter() - t0) / (n * reps) * 1e6
        tokens = int(seq.sum())
        least = tokens * (rank + dr) * 2 / PEAK_BYTES_PER_S * 1e6
        pool_least = tokens * W * 2 / PEAK_BYTES_PER_S * 1e6
        # Rows of its page a row's write moves, in and out (a tree from
        # before the plan moves the page).
        moved = getattr(ld, "write_rows", lambda pool: ps)(pool)
        write_least = 2 * B * moved * W * 2 / PEAK_BYTES_PER_S * 1e6
        results.append({"lens": spec, "rows": rows, "tokens": tokens,
                        "us_per_call": us, "write_us_per_call": write_us,
                        "write_rows_moved": moved,
                        "write_least_us": write_least,
                        "latent_least_us": least,
                        "latent_roofline_pct": 100 * least / us,
                        "pool_roofline_pct": 100 * pool_least / us,
                        "finite": finite})
        print(f"  lens {spec}: attention {us:,.1f} us/call, write "
              f"{write_us:,.1f} ({B} rows x {moved} of {ps} rows of a "
              f"page in and out: {write_least:,.1f} us at peak); "
              f"latents at peak {least:,.1f} us = "
              f"{100 * least / us:.1f} % (the pool's padded rows: "
              f"{100 * pool_least / us:.1f} %)  finite={finite}",
              flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump({"config": doc["name"], "tree": args.tree,
                       "max_pages": mp, "results": results}, f, indent=1)


def bench_fused(args, doc) -> None:
    """The fused decode kernel of the Llama block (bf16 or int8 by the
    file) at the served geometry: µs a call, ``decode_work``'s
    schedule, and the share of the K/V bytes' time at 819 GB/s."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.ops.pallas import fused_decode

    ex, model = doc["server"]["executor"], doc["server"]["model"]
    H, Hkv = doc["num_attention_heads"], doc["num_key_value_heads"]
    D = doc.get("head_dim") or doc["hidden_size"] // H
    L, GD = doc["num_hidden_layers"], Hkv * D
    reps = REPS
    if args.rehearse:
        L, reps = 2, 2
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from this host is no device "
                 "number (--rehearse runs the path in interpret mode)")
    B, ps = ex["max_batch_size"], ex["page_size"]
    P = args.kv_pages or ex["kv_pages"]
    L = args.layers or L
    mp = args.max_pages or model["max_seq_len"] // ps
    q8 = model.get("kv_quantization") == "int8"
    itemsize = 1 if q8 else 2
    extra = {"window": args.window} if args.window else {}

    pools = random_pools(L, P, ps, GD, Hkv, q8)
    if q8:
        new = [jnp.ones((B, Hkv, D), jnp.int8), jnp.ones((B, Hkv),
                                                         jnp.bfloat16)] * 2
        kernel = fused_decode.fused_decode_attention_q8_pallas
    else:
        new = [jnp.ones((B, Hkv, D), jnp.bfloat16)] * 2
        kernel = fused_decode.fused_decode_attention_pallas
    q = jax.random.normal(jax.random.key(0), (B, H, D), jnp.bfloat16)

    def program(gathered):
        """``reps`` calls in one program; ``gathered``: q, the new rows
        and the output go through the order's gathers at every call, as
        ``ops/attention.paged_decode_step(order=...)`` has them."""

        @partial(jax.jit, donate_argnums=(0,))
        def many(pools, q, new, bt, seq_lens, write_page, rows, places):
            outs = []
            for i in range(reps):
                qi, newi = q, new
                if gathered:    # (a row of ``rows`` a call: no CSE)
                    qi, newi = q[rows[i]], [x[rows[i]] for x in new]
                # The int8 kernel takes its four pools as one argument.
                attn, pools = kernel(
                    qi, *newi, *((pools,) if q8 else pools), bt, seq_lens,
                    write_page, jnp.int32(i % L),
                    pages_per_chunk=args.pages_per_chunk,
                    interpret=args.rehearse, **extra)
                if gathered:
                    attn = attn[places[i]]
                outs.append(jnp.sum(attn.astype(jnp.float32)))
            return jnp.stack(outs), attn, pools
        return many

    plan = None
    if hasattr(fused_decode, "decode_work"):
        plan = fused_decode._tile_plan(  # noqa: SLF001 — the dev tool
            B, ps, mp, GD, itemsize, args.pages_per_chunk)
    print(f"{doc['name']}: B={B} H={H} Hkv={Hkv} D={D} ps={ps} "
          f"max_pages={mp} layers={L} {'int8' if q8 else 'bf16'} "
          f"window={args.window or None} plan={plan} "
          f"device={jax.devices()[0].device_kind}"
          f"{' REHEARSAL: times mean nothing' if args.rehearse else ''}",
          flush=True)
    rng = np.random.default_rng(0)
    results = []
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
    programs = {}
    for spec in args.lens:
        rows, seat_seq, seat_bt, seat_wp = occupancy(spec, B, ps, mp, P, rng,
                                                     args)
        for order in args.order or ["seat"]:
            # ``sorted``: the rows handed over longest first, ties by
            # row (ops/attention.decode_order), the kernel alone;
            # ``gathered``: the same through the gathers a call.
            by = (np.arange(B) if order == "seat"
                  else np.argsort(-seat_seq.astype(np.int64), kind="stable"))
            seq, bt, write_page = seat_seq[by], seat_bt[by], seat_wp[by]
            places = np.argsort(by)
            key = order == "gathered"
            if key not in programs:
                programs[key] = program(key)
            many = programs[key]
            call = tuple(jnp.asarray(x) for x in (
                bt, seq, write_page,
                np.tile(by.astype(np.int32), (reps, 1)),
                np.tile(places.astype(np.int32), (reps, 1))))
            if order == "sorted":   # the rows come in order
                call = (q[by], [x[by] for x in new]) + call
            else:
                call = (q, new) + call
            outs, attn, pools = many(pools, *call)
            first = np.asarray(attn, np.float32)  # the last call's
            if order == "sorted":                 # by batch row again
                first = first[places]
            t0 = time.perf_counter()
            n = 1 if args.rehearse else 10
            for _ in range(n):
                outs, attn, pools = many(pools, *call)
            jax.block_until_ready(outs)
            us = (time.perf_counter() - t0) / (n * reps) * 1e6
            seen = (np.minimum(seq, args.window) if args.window
                    else seq)
            kv_bytes = int(seen.sum()) * (2 * GD * itemsize
                                          + (4 * Hkv if q8 else 0))
            least_us = kv_bytes / PEAK_BYTES_PER_S * 1e6
            work = (fused_decode.decode_work(
                seq, plan, *([args.window] if args.window else []))
                if plan else None)
            results.append({
                "lens": spec, "order": order, "rows": rows,
                "tokens": int(seq.sum()),
                "us_per_call": us, "decode_work": work,
                "kv_least_us": least_us,
                "kv_roofline_pct": 100 * least_us / us,
                "finite": bool(np.isfinite(first).all())})
            if args.out:
                np.save(f"{args.out}.{len(results) - 1}.npy", first)
            print(f"  lens {spec} order={order}: "
                  f"{us:,.1f} us/call  decode_work "
                  f"(steps, computed, live, in full steps)={work}  "
                  f"K/V bytes at peak {least_us:,.1f} us = "
                  f"{100 * least_us / us:.1f} %  finite="
                  f"{results[-1]['finite']}", flush=True)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump({"config": doc["name"], "tree": args.tree,
                       "plan": plan and plan._asdict(), "max_pages": mp,
                       "window": args.window or None, "layers": L,
                       "results": results}, f, indent=1)


def bench_prefill(args, doc) -> None:
    """One slice of the mixed step through the paged prefill attention
    kernel (``--prefill LENGTH@START``): µs a call, the plan's steps,
    and the least time of the products and of the bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.ops import attention
    from llmq_tpu.ops.pallas import prefill_attention as pa

    ex, model = doc["server"]["executor"], doc["server"]["model"]
    H, Hkv = doc["num_attention_heads"], doc["num_key_value_heads"]
    D = doc.get("head_dim") or doc["hidden_size"] // H
    L, GD = doc["num_hidden_layers"], Hkv * D
    reps = REPS
    if args.rehearse:
        L, reps = 2, 2
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from this host is no device "
                 "number (--rehearse runs the path in interpret mode)")
    ps, P = ex["page_size"], ex["kv_pages"]
    mp = args.max_pages or model["max_seq_len"] // ps
    mixed = ex["mixed_batch"]
    T = mixed["prefill_token_budget"] // mixed["max_slices"]
    q8 = model.get("kv_quantization") == "int8"
    itemsize = 1 if q8 else 2
    pools = random_pools(L, P, ps, GD, Hkv, q8)
    q = jax.random.normal(jax.random.key(0), (T, H, D), jnp.bfloat16)
    plan = pa.prefill_tile_plan(T, H, Hkv, D, ps, mp, itemsize,
                                q_itemsize=2 if q8 else 0)

    def kernel(q, pools, bt, start, length, layer):
        if q8:
            return pa.paged_prefill_attention_q8_pallas(
                q, pools, bt, start, length, layer, interpret=args.rehearse)
        return pa.paged_prefill_attention_pallas(
            q, *pools, bt, start, layer, interpret=args.rehearse)

    def pure(q, pools, bt, start, length, layer):
        positions = (start + jnp.arange(T, dtype=jnp.int32))[None]
        hist = [attention._dequant_window(  # noqa: SLF001 — the dev tool
            pools[i], pools[2 + i], layer, bt[None], D) for i in range(2)]
        return attention.blockwise_prefill_attention(
            q[None], *hist, positions, (start + length)[None])[0]

    def many(fn):
        @jax.jit
        def run(pools, bt, start, length):
            outs = []
            for i in range(reps):
                # A query of its own a call: equal calls are one to XLA.
                o = fn(q * (1 + i / 64), pools, bt, start, length,
                       jnp.int32(i % L))
                outs.append(jnp.sum(o.astype(jnp.float32)))
            return jnp.stack(outs), o
        return run

    paths = [("kernel", many(kernel))]
    if args.pure and q8:
        paths.append(("xla", many(pure)))
    print(f"{doc['name']}: prefill slice T={T} H={H} Hkv={Hkv} D={D} "
          f"ps={ps} max_pages={mp} {'int8' if q8 else 'bf16'} plan={plan} "
          f"device={jax.devices()[0].device_kind}"
          f"{' REHEARSAL: times mean nothing' if args.rehearse else ''}",
          flush=True)
    rng = np.random.default_rng(0)
    n = 1 if args.rehearse else 10
    results = []
    for spec in args.prefill:
        length, _, start = spec.partition("@")
        length, start = int(length), int(start or 0)
        if length > T or start + length > mp * ps:
            sys.exit(f"--prefill {spec}: a slice holds {T} tokens and a "
                     f"block table {mp * ps}")
        bt = np.zeros(mp, np.int32)
        live = -(-(start + length) // ps)
        bt[:live] = 1 + rng.permutation(P - 1)[:live]
        call = (jnp.asarray(bt), jnp.int32(start), jnp.int32(length))
        # each valid query against what it sees: QK^T and PV
        pairs = sum(start + t + 1 for t in range(length))
        flops = 2 * 2 * pairs * H * D
        nbytes = ((start + length) * (2 * GD * itemsize
                                      + (4 * Hkv if q8 else 0))
                  + 2 * length * H * D * 2)
        least = max(flops / PEAK_FLOPS_PER_S, nbytes / PEAK_BYTES_PER_S) * 1e6
        rec = {"prefill": spec, "length": length, "start": start,
               "steps": plan.steps(T, start, length if q8 else None),
               "flops_least_us": flops / PEAK_FLOPS_PER_S * 1e6,
               "bytes_least_us": nbytes / PEAK_BYTES_PER_S * 1e6}
        for name, run in paths:
            outs, o = run(pools, *call)
            valid = np.asarray(o, np.float32)[:length]
            t0 = time.perf_counter()
            for _ in range(n):
                outs, o = run(pools, *call)
            jax.block_until_ready(outs)
            rec[f"{name}_us_per_call"] = (
                time.perf_counter() - t0) / (n * reps) * 1e6
            rec[f"{name}_finite"] = bool(np.isfinite(valid).all())
            if name == "kernel":
                first = valid
            else:
                rec["max_abs_diff"] = (float(np.abs(first - valid).max())
                                       if length else 0.0)
        results.append(rec)
        us = rec["kernel_us_per_call"]
        print(f"  prefill {length} tokens at {start}: {us:,.1f} us/call  "
              f"steps={rec['steps']}  products at peak "
              f"{rec['flops_least_us']:,.1f} us, bytes at peak "
              f"{rec['bytes_least_us']:,.1f} us = {100 * least / us:.1f} %  "
              f"finite={rec['kernel_finite']}"
              + (f"  xla {rec['xla_us_per_call']:,.1f} us/call, max |diff| "
                 f"{rec['max_abs_diff']:.4f}" if "xla_us_per_call" in rec
                 else ""), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(f"{args.out}.prefill.json", "w", encoding="utf-8") as f:
            json.dump({"config": doc["name"], "tree": args.tree,
                       "plan": plan._asdict(), "max_pages": mp,
                       "results": results}, f, indent=1)


def bench_latent_prefill(args, doc) -> None:
    """One slice of a latent family's mixed step through
    ``models/latent.latent_prefill_attention`` (``--prefill
    LENGTH@START``; XLA: expanded K and V from the cached latents): µs
    a call beside the least time of the work that is there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from types import SimpleNamespace

    from llmq_tpu.models import latent

    class Dims(latent.LatentDims, SimpleNamespace):
        """What the attention reads of a configuration."""

    ex, model = doc["server"]["executor"], doc["server"]["model"]
    cfg = Dims(dim=doc["hidden_size"], n_heads=doc["num_attention_heads"],
               kv_lora_rank=doc["kv_lora_rank"],
               q_lora_rank=doc.get("q_lora_rank"),
               qk_nope_head_dim=doc["qk_nope_head_dim"],
               qk_rope_head_dim=doc["qk_rope_head_dim"],
               v_head_dim=doc["v_head_dim"],
               mla_scale_q_lora=bool(doc.get("mla_scale_q_lora")),
               mla_scale_kv_lora=bool(doc.get("mla_scale_kv_lora")),
               dtype=jnp.bfloat16)
    H, r, dr = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv, W = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.latent_width
    L = doc.get("num_hidden_layers") or 2 * doc["num_layers"]
    reps = 8
    if args.rehearse:
        L, reps = 2, 2
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from this host is no device "
                 "number (--rehearse runs the path on this host)")
    ps, P = ex["page_size"], ex["kv_pages"]
    mp = args.max_pages or model["max_seq_len"] // ps
    mixed = ex["mixed_batch"]
    T = mixed["prefill_token_budget"] // mixed["max_slices"]
    key = jax.random.key(0)
    pool = jnp.tile(jax.random.normal(key, (1, P, ps, W), jnp.bfloat16),
                    (L, 1, 1, 1)) * 0.5
    lp = {"wkv_b": jax.random.normal(key, (L, r, H * (dn + dv)),
                                     jnp.bfloat16) * r ** -0.5}
    q_nope = jax.random.normal(key, (1, T, H, dn), jnp.bfloat16)
    q_rope = jax.random.normal(key, (1, T, H, dr), jnp.bfloat16)

    @jax.jit
    def run(pool, bt, positions, seq_lens):
        outs = []
        for i in range(reps):
            # A query of its own a call: equal calls are one to XLA.
            o = latent.latent_prefill_attention(
                cfg, lp, i % L, q_nope * (1 + i / 64), q_rope, pool, bt,
                positions, seq_lens)
            outs.append(jnp.sum(o.astype(jnp.float32)))
        return jnp.stack(outs), o

    print(f"{doc['name']}: latent prefill slice T={T} H={H} rank={r} "
          f"ps={ps} max_pages={mp} tree={args.tree} "
          f"device={jax.devices()[0].device_kind}"
          f"{' REHEARSAL: times mean nothing' if args.rehearse else ''}",
          flush=True)
    rng = np.random.default_rng(0)
    n = 1 if args.rehearse else 10
    results = []
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
    for spec in args.prefill:
        length, _, start = spec.partition("@")
        length, start = int(length), int(start or 0)
        if not 0 < length <= T or start + length > mp * ps:
            sys.exit(f"--prefill {spec}: a slice holds 1 to {T} tokens "
                     f"and a block table {mp * ps}")
        ctx = start + length
        bt = np.zeros((1, mp), np.int32)
        live = -(-ctx // ps)
        bt[0, :live] = 1 + rng.permutation(P - 1)[:live]
        positions = start + np.minimum(np.arange(T), length - 1)[None]
        call = (jnp.asarray(bt), jnp.asarray(positions, jnp.int32),
                jnp.asarray([ctx], jnp.int32))
        outs, o = run(pool, *call)
        finite = bool(np.isfinite(np.asarray(o, np.float32)).all())
        t0 = time.perf_counter()
        for _ in range(n):
            outs, o = run(pool, *call)
        jax.block_until_ready(outs)
        us = (time.perf_counter() - t0) / (n * reps) * 1e6
        # K and V expanded from the context's latents, then each valid
        # query against what it sees: QK^T (nope and rope) and PV
        pairs = sum(start + t + 1 for t in range(length))
        flops = (2 * ctx * r * H * (dn + dv)
                 + 2 * pairs * H * (dn + dr + dv))
        nbytes = (ctx * W * 2 + r * H * (dn + dv) * 2
                  + length * H * (dn + dr + dv) * 2)
        flops_us = flops / PEAK_FLOPS_PER_S * 1e6
        bytes_us = nbytes / PEAK_BYTES_PER_S * 1e6
        results.append({"prefill": spec, "length": length, "start": start,
                        "us_per_call": us, "finite": finite,
                        "flops_least_us": flops_us,
                        "bytes_least_us": bytes_us})
        print(f"  prefill {length} tokens at {start}: {us:,.1f} us/call  "
              f"products at peak {flops_us:,.1f} us, "
              f"bytes at peak {bytes_us:,.1f} us = "
              f"{100 * max(flops_us, bytes_us) / us:.1f} %  finite={finite}",
              flush=True)
    if args.out:
        with open(f"{args.out}.prefill.json", "w", encoding="utf-8") as f:
            json.dump({"config": doc["name"], "tree": args.tree,
                       "max_pages": mp, "results": results}, f, indent=1)


def _save(args, doc, results) -> None:
    """``--out``: a bench's records, with the tree they were read in."""
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump({"config": doc["name"], "tree": args.tree,
                       "results": results}, f, indent=1)


#: family (the configuration file's ``family``, ``llmq_tpu/models``
#: ``FAMILIES``) -> the bench of its decode kernels. The kernels a
def bench_ssm(args, doc) -> None:
    """The Mamba-2 decode state update of one layer at the served
    geometry (``--lens ROWS``: that many of the batch's rows decode, the
    others keep their state): µs a call through the in-place kernel
    (``ops/pallas/ssm_update.py``) and through XLA's fusion of
    ``ops/ssm.ssm_update``, each over the stacked leaf with the pool
    donated, and the share of the LIVE rows' state read once and written
    once at 819 GB/s (the yardstick of ``ssm_update_roofline``), beside
    the block a step of the kernel's walk holds and how many it takes.
    ``--lanes N`` times the kernel again with its shape rule's VMEM
    budget set so that it picks N-lane blocks (the sweep behind the
    rule: PERF.md section 6, PR 40)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.ops import ssm
    from llmq_tpu.ops.pallas import ssm_update as su

    ex = doc["server"]["executor"]
    B = ex["max_batch_size"]
    H, P, N = doc["mamba_n_heads"], doc["mamba_d_head"], doc["mamba_d_state"]
    L = doc["layer_types"].count("mamba")
    reps = L
    if args.rehearse:
        os.environ["LLMQ_PALLAS"] = "interpret"
        L = reps = 2
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from this host is no device "
                 "number (--rehearse runs the path in interpret mode)")
    key = jax.random.key(0)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (B, H, P), jnp.float32)
    dt = jnp.exp(jax.random.uniform(ks[1], (B, H), jnp.float32, -6.9, -2.3))
    a = -jax.random.uniform(ks[2], (H,), jnp.float32, 1.0, 16.0)
    bm = jax.random.normal(ks[3], (B, N), jnp.float32)
    cm = jax.random.normal(ks[4], (B, N), jnp.float32)
    d = jnp.ones((H,), jnp.float32)

    def make(enabled):
        # One rolled loop over the layers (the layer index traced: the
        # kernel reads it from its scalar prefetch, XLA's path slices
        # and updates the leaf dynamically): 36 unrolled XLA updates of
        # one donated leaf made the compiler keep a copy of the leaf a
        # call (141 GB asked of the chip's 15.75).
        @partial(jax.jit, donate_argnums=(0,))
        def run(pool, active, x, dt, a, bm, cm, d):
            def body(i, carry):
                pool, acc = carry
                # inputs of its own a call
                y, pool = ssm.ssm_update_layer(
                    pool, i % L, x * (1 + i / 64), dt, a, bm, cm, d, active,
                    enabled=enabled)
                return pool, acc + y
            return jax.lax.fori_loop(
                0, reps, body, (pool, jnp.zeros((B, H, P), jnp.float32)))
        return run

    print(f"{doc['name']}: ssm update B={B} heads={H}x{P} state={N} "
          f"layers={L} device={jax.devices()[0].device_kind}"
          f"{' REHEARSAL: times mean nothing' if args.rehearse else ''}",
          flush=True)
    n = 1 if args.rehearse else 10

    def timed(enabled, active):
        run = make(enabled)
        # (one layer's draw tiled: a draw of the whole 4.9 GB leaf
        # needs as much again for its bits)
        pool = jnp.tile(jax.random.normal(
            ks[5], (1, B + 1, N, H * P), jnp.float32), (L, 1, 1, 1))
        call = (active, x, dt, a, bm, cm, d)
        pool, y = run(pool, *call)
        first = np.asarray(y)
        t0 = time.perf_counter()
        for _ in range(n):
            pool, y = run(pool, *call)
        jax.block_until_ready(y)
        return (time.perf_counter() - t0) / (n * reps) * 1e6, first

    results = []
    W = H * P
    before_pr40 = not hasattr(su, "STATE_VMEM_BYTES")   # --tree <a parent>
    xla = {}            # rows -> (us, y): XLA's knows nothing of the block
    for lanes, spec in ((l, s) for l in (args.lanes or [0])
                        for s in args.lens):
        rows = min(B, int(spec.split("x")[0]))
        active = jnp.arange(B) < rows
        if lanes:
            su.STATE_VMEM_BYTES = su.SLOTS * N * lanes * 4
        block = su._lanes(W) if before_pr40 else su._lanes(N, W)
        rec = {"rows": rows, "block": [N, block],
               "steps": (B if before_pr40 else rows) * (W // block)}
        rec["kernel_us"], kernel_y = timed(True, active)
        if rows not in xla:
            xla[rows] = timed(False, active)
        rec["xla_us"], xla_y = xla[rows]
        least = rows * 2 * N * H * P * 4 / PEAK_BYTES_PER_S * 1e6
        gap = float(np.abs(kernel_y[:rows] - xla_y[:rows]).max())
        rec.update(least_us=least, max_abs_gap=gap,
                   kernel_roofline_pct=100 * least / rec["kernel_us"],
                   xla_roofline_pct=100 * least / rec["xla_us"])
        results.append(rec)
        print(f"  rows {rows:3d} of {B}: {rec['steps']} steps of a "
              f"({N}, {block}) block: kernel {rec['kernel_us']:,.1f} "
              f"us/call ({rec['kernel_roofline_pct']:.1f} % of the live "
              f"rows' bytes at peak), xla {rec['xla_us']:,.1f} "
              f"({rec['xla_roofline_pct']:.1f} %); least {least:,.1f}; "
              f"outputs apart by {gap:.2e}", flush=True)
    _save(args, doc, results)


def _kda_geometry(doc):
    """``(heads, head_dim, KDA layers held, draw)`` of a configuration
    with delta-rule layers, by its family's keys; ``draw(key_g, key_b,
    shape)`` the log-decay and beta as the family makes them:
    ``ling_hybrid`` the bounded decay and beta in (0, 1), ``solar_open2``
    Kimi's unbounded softplus form and beta in (0, 2)."""
    import jax

    if doc["family"] == "solar_open2":
        lin = doc["linear_attn_config"]
        n = doc["num_hidden_layers"]
        L = n - sum(1 for l in doc["gqa_layers"] if l < n)

        def draw(kg, kb, shape):
            return (-2.0 * jax.nn.softplus(
                        3.0 * jax.random.normal(kg, shape) - 3.0),
                    2.0 * jax.nn.sigmoid(jax.random.normal(kb, shape[:-1])))
        return lin["num_heads"], lin["head_dim"], L, draw
    L = sum(1 for l in range(doc["num_hidden_layers"])
            if (l + 1) % doc["layer_group_size"])

    def draw(kg, kb, shape):
        return (doc["kda_lower_bound"] * jax.nn.sigmoid(
                    2.0 * jax.random.normal(kg, shape) - 2.5),
                jax.nn.sigmoid(jax.random.normal(kb, shape[:-1])))
    return doc["num_attention_heads"], doc["head_dim"], L, draw


def bench_kda(args, doc) -> None:
    """The delta rule's decode state update of one layer at the served
    geometry (``--lens ROWS``: that many of the batch's rows decode):
    µs a call through the in-place kernel (``ops/pallas/kda_update.py``)
    and through XLA's fusion of ``ops/kda.kda_update``, each over the
    stacked leaf with the pool donated, and the share of the LIVE rows'
    state read once and written once at 819 GB/s (the yardstick of
    ``ssm_update_roofline``). ``bench_ssm``'s procedure."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.ops import kda

    ex = doc["server"]["executor"]
    B = ex["max_batch_size"]
    H, d, L, draw = _kda_geometry(doc)
    reps = L
    if args.rehearse:
        os.environ["LLMQ_PALLAS"] = "interpret"
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from this host is no device "
                 "number (--rehearse runs the path in interpret mode)")
    ks = jax.random.split(jax.random.key(0), 6)
    q = kda.l2_norm(jax.random.normal(ks[0], (B, H, d))) * d ** -0.5
    k = kda.l2_norm(jax.random.normal(ks[1], (B, H, d)))
    v = jax.random.normal(ks[2], (B, H, d), jnp.float32)
    g, beta = draw(ks[3], ks[4], (B, H, d))

    def make(enabled):
        @partial(jax.jit, donate_argnums=(0,))
        def run(pool, active, q, k, v, g, beta):
            def body(i, carry):
                pool, acc = carry
                o, pool = kda.kda_update_layer(
                    pool, i % L, q, k, v * (1 + i / 64), g, beta, active,
                    enabled=enabled)
                return pool, acc + o
            return jax.lax.fori_loop(
                0, reps, body, (pool, jnp.zeros((B, H, d), jnp.float32)))
        return run

    print(f"{doc['name']}: kda update B={B} heads={H}x{d} layers={L} "
          f"device={jax.devices()[0].device_kind}"
          f"{' REHEARSAL: times mean nothing' if args.rehearse else ''}",
          flush=True)
    n = 1 if args.rehearse else 10

    def timed(enabled, active):
        run = make(enabled)
        pool = jnp.tile(jax.random.normal(
            ks[5], (1, B + 1, d, H * d), jnp.float32), (L, 1, 1, 1))
        call = (active, q, k, v, g, beta)
        pool, o = run(pool, *call)
        first = np.asarray(o)
        t0 = time.perf_counter()
        for _ in range(n):
            pool, o = run(pool, *call)
        jax.block_until_ready(o)
        return (time.perf_counter() - t0) / (n * reps) * 1e6, first

    results = []
    for spec in args.lens:
        rows = min(B, int(spec.split("x")[0]))
        active = jnp.arange(B) < rows
        rec = {"rows": rows}
        rec["kernel_us"], kernel_o = timed(True, active)
        rec["xla_us"], xla_o = timed(False, active)
        least = rows * 2 * d * H * d * 4 / PEAK_BYTES_PER_S * 1e6
        gap = float(np.abs(kernel_o[:rows] - xla_o[:rows]).max())
        rec.update(least_us=least, max_abs_gap=gap,
                   kernel_roofline_pct=100 * least / rec["kernel_us"],
                   xla_roofline_pct=100 * least / rec["xla_us"])
        results.append(rec)
        print(f"  rows {rows:3d} of {B}: kernel {rec['kernel_us']:,.1f} "
              f"us/call ({rec['kernel_roofline_pct']:.1f} % of the live "
              f"rows' bytes at peak), xla {rec['xla_us']:,.1f} "
              f"({rec['xla_roofline_pct']:.1f} %); least {least:,.1f}; "
              f"outputs apart by {gap:.2e}", flush=True)
    _save(args, doc, results)


def bench_kda_scan(args, doc) -> None:
    """The delta rule's scan of one layer over a mixed step's prompt
    slices at the served geometry (``--prefill L1,L2,...``: the valid
    tokens of each slice, the slices left over empty): µs a call through
    the kernel (``ops/pallas/kda_scan.py``; ``--lanes N`` again at N / 128
    heads a grid step) and through XLA's scan (``ops/kda.kda_scan``),
    the 64-token chunks under a length of the grid's, how far the two
    are apart at the valid positions and in the states handed on, and
    how far each is from the update applied a token at a time in float64
    on the host (one head of the first slice). A tree without the kernel
    (``--tree``) times XLA's scan alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.ops import kda

    mb = doc["server"]["executor"]["mixed_batch"]
    S = mb["max_slices"]
    T = mb["prefill_token_budget"] // S
    H, d, L, draw = _kda_geometry(doc)
    block = 16                  # the families' adapter.KDA_CHUNK
    if args.rehearse:
        os.environ["LLMQ_PALLAS"] = "interpret"
        S, T, H, L = 2, 128, 2, 1
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from this host is no device "
                 "number (--rehearse runs the path in interpret mode)")
    ks = jax.random.split(jax.random.key(0), 6)
    q = kda.l2_norm(jax.random.normal(ks[0], (S, T, H, d))) * d ** -0.5
    k = kda.l2_norm(jax.random.normal(ks[1], (S, T, H, d)))
    v = jax.random.normal(ks[2], (S, T, H, d), jnp.float32)
    g, beta = draw(ks[3], ks[4], (S, T, H, d))
    state = jax.random.normal(ks[5], (S, d, H * d), jnp.float32)
    kernel = getattr(kda, "kda_scan_slices", None)

    def make(heads):
        @jax.jit
        def run(state, lengths, q, k, v, g, beta):
            def body(i, carry):
                st, acc = carry
                # (nothing of a call is the loop's invariant to hoist)
                one = jnp.where(i < 1 << 20, 1.0, 2.0)
                call = (state, q * one, k * one, v * (1 + i / 64), g * one,
                        beta, lengths)
                if heads is None:
                    o, st = kda.kda_scan(*call, block)
                elif heads:
                    from llmq_tpu.ops.pallas.kda_scan import kda_scan_pallas
                    o, st = kda_scan_pallas(*call, block=block, heads=heads,
                                            interpret=args.rehearse)
                else:
                    o, st = kernel(*call, block)
                return st, acc + o
            return jax.lax.fori_loop(0, L, body,
                                     (state, jnp.zeros_like(v)))
        return run

    print(f"{doc['name']}: kda scan S={S} T={T} heads={H}x{d} layers={L} "
          f"device={jax.devices()[0].device_kind}"
          f"{' REHEARSAL: times mean nothing' if args.rehearse else ''}",
          flush=True)
    n = 1 if args.rehearse else 10

    def timed(heads, lengths):
        run = make(heads)
        call = (state, lengths, q, k, v, g, beta)
        st, o = run(*call)
        first = (np.asarray(o), np.asarray(st))
        t0 = time.perf_counter()
        for _ in range(n):
            st, o = run(*call)
        jax.block_until_ready(o)
        return (time.perf_counter() - t0) / (n * L) * 1e6, first

    def reference(length, h=0):
        """The state behind slice 0 of head ``h``, a token at a time in
        float64; the last layer's call (``v`` scaled as ``make`` does)."""
        f = lambda x: np.asarray(x[0, :, h], np.float64)  # noqa: E731
        kk, gg = f(k), f(g)
        vv = np.asarray(v[0, :, h] * (1 + (L - 1) / 64), np.float64)
        bb = np.asarray(beta[0, :, h], np.float64)
        st = np.asarray(state[0, :, h * d:(h + 1) * d], np.float64)
        for t in range(length):
            st = st * np.exp(gg[t])[:, None]
            st = st + np.outer(kk[t] * bb[t], vv[t] - kk[t] @ st)
        return st

    results = []
    for spec in args.prefill:
        lens = [min(T, int(x)) for x in spec.split(",")][:S]
        lens += [0] * (S - len(lens))
        lengths = jnp.asarray(lens, jnp.int32)
        valid = np.arange(T)[None] < np.asarray(lens)[:, None]
        chunks = sum(-(-x // 64) for x in lens)
        rec = {"lengths": lens, "chunks": S * T // 64, "chunks_live": chunks}
        rec["xla_us"], (xla_o, xla_s) = timed(None, lengths)
        line = (f"  lengths {lens}: {chunks} of {rec['chunks']} chunks "
                f"live: xla {rec['xla_us']:,.1f} us/call")
        # (every call starts from ``state``: the last one's is returned)
        ref_s = reference(lens[0])

        def against(s):
            return float(np.abs(s[0, :, :d] - ref_s).max())

        rec["xla_state_err"] = against(xla_s)
        line += f" (state off float64 by {rec['xla_state_err']:.2e})"
        if kernel is not None:
            for heads in [0] + [x // 128 for x in args.lanes]:
                us, (ker_o, ker_s) = timed(heads, lengths)
                tag = f"kernel{heads or ''}"
                rec[f"{tag}_us"] = us
                rec[f"{tag}_state_err"] = against(ker_s)
                rec[f"{tag}_gap_o"] = float(
                    np.abs(ker_o - xla_o)[valid].max()) if chunks else 0.0
                rec[f"{tag}_gap_state"] = float(np.abs(ker_s - xla_s).max())
                line += (f"; {tag} {us:,.1f} (off float64 by "
                         f"{rec[f'{tag}_state_err']:.2e}; from xla: outputs "
                         f"{rec[f'{tag}_gap_o']:.2e}, states "
                         f"{rec[f'{tag}_gap_state']:.2e})")
        results.append(rec)
        print(line, flush=True)
    _save(args, doc, results)


def routed_widths(doc):
    """(hidden, expert width, experts held, experts a token, experts the
    router scores) of a configuration with routed experts, under the
    names its family's file gives them."""
    def first(*names):
        for name in names:
            if doc.get(name):
                return doc[name]
        sys.exit(f"{doc.get('name')}: no routed experts (none of {names})")
    E = first("n_routed_experts", "num_experts", "num_local_experts")
    return (doc["hidden_size"],
            first("moe_intermediate_size", "expert_ffn_hidden_size"), E,
            first("num_experts_per_tok", "moe_topk"),
            doc.get("router_experts", E) + doc.get("zero_expert_num", 0))


def _rule(moe, m, K, N):
    """The tiles the tree's ``ops/moe.py`` gives the product: its rule's,
    or a parent's constant."""
    rule = getattr(moe, "gmm_tiling", None)
    return rule(m, K, N) if rule else moe.GMM_TILING


def bench_tiles(args, doc) -> None:
    """The two grouped products of one routed layer ALONE at the served
    widths, over the sorted pairs that ``--lens ROWS`` tokens send to
    the held experts (each token the top ``k`` of random scores over
    the router's experts): the megablox kernel at each ``--tiling`` and
    at the rule's tiles (``ops/moe.gmm_tiling``; a parent's constant).
    Four layers' matrices, each a leaf of its own, are multiplied in
    turn so that every call reads its matrices from memory."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from llmq_tpu.ops import moe

    D, F, E, k, R = routed_widths(doc)
    L, n, interpret = 4, 10, False
    if args.rehearse:
        D, F, E, R, L, n, interpret = 256, 128, min(E, 8), min(R, 8), 2, 1, True
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from this host is no device "
                 "number (--rehearse runs the path in interpret mode)")
    ks = jax.random.split(jax.random.key(0), 2 + 2 * L)
    w = {"gate-up": [jax.random.normal(ks[2 + i], (E, D, 2 * F), jnp.bfloat16)
                     / D ** 0.5 for i in range(L)],
         "down": [jax.random.normal(ks[2 + L + i], (E, F, D), jnp.bfloat16)
                  / F ** 0.5 for i in range(L)]}
    print(f"{doc['name']}: grouped products alone D={D} F={F} held={E} of "
          f"{R} k={k} layers={L} device={jax.devices()[0].device_kind}"
          f"{' REHEARSAL: times mean nothing' if args.rehearse else ''}",
          flush=True)
    given = [tuple(int(v) for v in t.split(",")) for t in args.tiling]
    rng = np.random.default_rng(0)
    results = []
    for spec in args.lens:
        rows = int(spec)
        chosen = np.argsort(rng.random((rows, R)), axis=1)[:, :k]
        counts = np.bincount(chosen[chosen < E], minlength=E).astype(np.int32)
        pairs = int(counts.sum())
        ends = np.cumsum(counts)
        groups = [(e - c, e) for c, e in zip(counts, ends) if c]
        for name, (K, N) in (("gate-up", (D, 2 * F)), ("down", (F, D))):
            xs = jax.random.normal(ks[0], (pairs, K), jnp.bfloat16)
            seen = []
            for tiles in [_rule(moe, pairs, K, N)] + given:
                tm = tiles[0]
                if tiles in seen or (args.rehearse and (
                        tiles[1] > K or tiles[2] > N)):
                    continue
                seen.append(tiles)
                lhs = jnp.pad(xs, ((0, -pairs % tm), (0, 0)))

                @jax.jit
                def run(lhs, ws, counts, tiles=tiles):
                    return sum(gmm(lhs, a, counts, tiling=tiles,
                                   preferred_element_type=lhs.dtype,
                                   interpret=interpret) for a in ws)

                visits = int(sum(-(-e // tm) - s // tm for s, e in groups))
                try:
                    jax.block_until_ready(run(lhs, w[name], counts))
                except Exception as e:  # a tile the compiler refuses
                    print(f"  {rows:5d} rows {name:7s} {tiles}: refused: "
                          f"{str(e).splitlines()[0][:200]}", flush=True)
                    continue
                t0 = time.perf_counter()
                for _ in range(n):
                    y = run(lhs, w[name], counts)
                jax.block_until_ready(y)
                us = (time.perf_counter() - t0) / (n * L) * 1e6
                least = len(groups) * K * N * 2 / PEAK_BYTES_PER_S
                rec = {"rows": rows, "pairs": pairs, "product": name,
                       "K": K, "N": N, "tiling": list(tiles), "us": us,
                       "visits": visits, "us_per_visit": us / max(visits, 1),
                       "bytes_share": least * 1e6 / us}
                results.append(rec)
                print(f"  {rows:5d} rows {pairs:6d} pairs {name:7s} "
                      f"{K}x{N} {str(tiles):18s}: {us:9,.1f} us/call, "
                      f"{visits:4d} visits, {rec['us_per_visit']:6.2f} "
                      f"us/visit, {rec['bytes_share']:6.1%} of the visited "
                      f"experts' bytes", flush=True)
    _save(args, doc, results)


#: family dispatches are what this tool is about, so a new family's
#: bench is a function here and an entry in this table.
def bench_routed(args, doc) -> None:
    """``ops/moe.routed_ffn`` of one layer at the served widths over the
    rows of a mixed step (``max_batch_size`` + ``prefill_token_budget``
    of them, ``--lens ROWS`` live and lying first): µs a call of the
    plain form (a ``live`` mask: every array N k rows long) and of the
    form that is told the count (``n_live``: a block of sorted pairs at
    a time while live pairs are left, then a tile of tokens at a time
    gathering each token's results), the latter at each ``--block`` and
    ``--tile`` (``moe.LIVE_BLOCK`` and ``moe.LIVE_TILE`` where none is
    given), and how far the two results lie apart. A file that holds a
    SHARE of the experts its
    router scores (``router_experts``) runs the share form (``held``,
    ``n_routed``: blocks of ``moe.HELD_BLOCK`` held pairs), which no
    count is told. Four layers' experts, each a leaf of its own, are
    multiplied in turn, so that every call reads its matrices from
    memory."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.ops import moe

    ex = doc["server"]["executor"]
    N = ex["max_batch_size"] + ex["mixed_batch"]["prefill_token_budget"]
    D, F, E, k, R = routed_widths(doc)
    share = ({"held": (0, E), "n_routed": doc.get("router_experts", E)}
             if R != E else {})
    L = 4
    if args.rehearse:
        os.environ["LLMQ_PALLAS"] = "interpret"
        N, D, F, L = 160, 256, 128, 2
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU here: a time from this host is no device "
                 "number (--rehearse runs the path in interpret mode)")
    ks = jax.random.split(jax.random.key(0), 3 + 2 * L)
    x = jax.random.normal(ks[0], (N, D), jnp.bfloat16)
    experts, gates = moe.route(
        x, jax.random.normal(ks[1], (D, R), jnp.float32) / D ** 0.5,
        jnp.zeros((R,), jnp.float32), top_k=k, scale=1.0, scoring="softmax")
    w_in = [jax.random.normal(ks[3 + i], (E, D, 2 * F), jnp.bfloat16)
            / D ** 0.5 for i in range(L)]
    w_out = [jax.random.normal(ks[3 + L + i], (E, F, D), jnp.bfloat16)
             / F ** 0.5 for i in range(L)]

    def make(told):
        @jax.jit
        def run(x, experts, gates, w_in, w_out, rows):
            live = jnp.arange(N) < rows
            y = jnp.zeros((N, D), jnp.float32)
            for a, b in zip(w_in, w_out):
                y = y + moe.routed_ffn(
                    x, experts, gates, a, b, live,
                    **({"n_live": rows} if told else share))[0]
            return y
        return run

    print(f"{doc['name']}: routed experts N={N} D={D} F={F} E={E} of {R} "
          f"k={k} layers={L} device={jax.devices()[0].device_kind}"
          f"{' REHEARSAL: times mean nothing' if args.rehearse else ''}",
          flush=True)
    for m in ([moe.HELD_BLOCK] if share else sorted(
            {N * k, min(moe.LIVE_BLOCK, -(-N * k // 128) * 128)})):
        print(f"  tiles at {m} pairs: gate-up {_rule(moe, m, D, 2 * F)}, "
              f"down {_rule(moe, m, F, D)}", flush=True)
    n = 1 if args.rehearse else 10

    def timed(told, rows):
        run = make(told)
        call = (x, experts, gates, w_in, w_out, jnp.int32(rows))
        first = np.asarray(run(*call))
        t0 = time.perf_counter()
        for _ in range(n):
            y = run(*call)
        jax.block_until_ready(y)
        return (time.perf_counter() - t0) / (n * L) * 1e6, first

    # (block, tile) of each told run: none for a share; no tile (0) for
    # a tree from before PR 59, whose sum by token scatter-adds
    tiles = (args.tile or [moe.LIVE_TILE]) if hasattr(moe, "LIVE_TILE") else [0]
    told = [] if share else [(b, t) for b in args.block or [moe.LIVE_BLOCK]
                             for t in tiles]
    results = []
    for spec in args.lens:
        rows = min(N, int(spec))
        rec = {"rows": rows, "of": N, "pairs": rows * k}
        rec["plain_us"], plain = timed(False, rows)
        line = f"  {rows:5d} rows live of {N}: plain {rec['plain_us']:,.1f}"
        for block, tile in told:
            moe.LIVE_BLOCK, key, of = block, f"block_{block}", f"{block}"
            if tile:
                moe.LIVE_TILE = tile
                key, of = key + f"_tile_{tile}", of + f", tiles of {tile}"
            us, got = timed(True, rows)
            gap = float(np.abs(got - plain).max())
            rec["live_us_" + key] = us
            rec["max_abs_gap"] = max(gap, rec.get("max_abs_gap", 0.0))
            line += f"; told, blocks of {of}: {us:,.1f} (apart {gap:.1e})"
        results.append(rec)
        print(line + " us/call", flush=True)
    _save(args, doc, results)


BENCHES = {"llama": bench_fused, "mellum": bench_routed, "deepseek_v3": bench_latent,
           "longcat_flash": bench_latent, "granitemoehybrid": bench_ssm,
           "ling_hybrid": bench_kda, "solar_open2": bench_kda}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model-file", required=True)
    ap.add_argument("--lens", action="append", default=[])
    ap.add_argument("--prefill", action="append", default=[],
                    metavar="LENGTH@START")
    ap.add_argument("--pure", action="store_true",
                    help="with --prefill over int8 pools: time the XLA "
                         "path the kernel replaced beside it")
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--max-pages", type=int, default=0)
    ap.add_argument("--pages-per-chunk", type=int, default=0)
    ap.add_argument("--spread", action="store_true")
    ap.add_argument("--shuffle", action="store_true")
    ap.add_argument("--order", action="append", default=[],
                    choices=["seat", "sorted", "gathered"],
                    help="how the rows reach the fused decode kernel "
                         "(repeatable; default seat)")
    ap.add_argument("--fused", action="store_true",
                    help="the fused decode kernel at the file's heads and "
                         "pages whatever its family (granite's attention "
                         "layers, Trinity's)")
    ap.add_argument("--window", type=int, default=0,
                    help="with --lens: the kernel under this window")
    ap.add_argument("--layers", type=int, default=0,
                    help="layers the bench's pools hold (default: the "
                         "file's)")
    ap.add_argument("--kv-pages", type=int, default=0)
    ap.add_argument("--lanes", action="append", type=int, default=[],
                    help="granitemoehybrid: time the update kernel at "
                         "this many lanes a step of its walk as well")
    ap.add_argument("--block", action="append", type=int, default=[],
                    help="routed: sorted pairs a block of the told form")
    ap.add_argument("--tile", action="append", type=int, default=[],
                    help="routed: tokens a tile of the told form's sum "
                         "by token")
    ap.add_argument("--routed", action="store_true",
                    help="the routed experts' bench at the file's widths "
                         "whatever its family")
    ap.add_argument("--tiling", action="append", default=[],
                    metavar="TM,TK,TN",
                    help="routed: time the two grouped products alone at "
                         "these tiles beside the rule's (repeatable)")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip: interpret mode, 2 layers, 2 "
                         "calls; the times mean nothing")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    with open(args.model_file, encoding="utf-8") as f:
        doc = json.load(f)
    if args.fused:
        doc["family"] = "llama"
    if args.routed or args.tiling:
        routed_widths(doc)         # exits where the file has no experts
        doc["family"] = "mellum"
    if doc.get("family") not in BENCHES:
        sys.exit(f"{args.model_file}: no kernel bench for the family "
                 f"{doc.get('family')!r}; known: {sorted(BENCHES)}")
    if args.prefill:
        if doc["family"] == "granitemoehybrid":
            sys.exit(f"--prefill: no slice bench for {doc['family']} (its "
                     f"recurrent mixer's scan is XLA's)")
        {"llama": bench_prefill, "ling_hybrid": bench_kda_scan,
         "solar_open2": bench_kda_scan}.get(
            doc["family"], bench_latent_prefill)(args, doc)
    if args.lens and args.tiling:
        bench_tiles(args, doc)
    elif args.lens:
        BENCHES[doc["family"]](args, doc)
    elif not args.prefill:
        ap.error("give --lens or --prefill")


if __name__ == "__main__":
    main()
