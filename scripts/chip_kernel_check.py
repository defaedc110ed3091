#!/usr/bin/env python3
"""Kernel phase of ``chip_smoke.py`` — the process that holds the chip
after the server child has exited.

At the geometry ``load_config()`` resolves (the same file and ``LLMQ_*``
environment the serve phase ran with, so the two agree by construction)
or, with ``--model-file``, at the geometry of a benchmark configuration
(``benchmark/configs/*.json``: its Hugging Face model block registered
as one more ``MODEL_CONFIGS`` entry, its ``server`` block as the
configuration file, ``LLMQ_*`` still on top — the float32 run does not
fit beside a deployment's pool and batch, so the SmolLM2 check runs
with ``LLMQ_EXECUTOR_KV_PAGES=512 LLMQ_EXECUTOR_MAX_BATCH_SIZE=8``):

(a) builds the engine through ``build_engine(cfg, warmup=True)`` — the
    programs the server compiled, loaded from the caches it left — and
    holds each compiled program's text against the routes the executor
    logged: a route that names a Pallas kernel must show Mosaic custom
    calls, that kernel's among them (``_kernel_route`` did not hand
    back the reference), a program whose routes are all ``xla`` must
    show none. On the shipped
    single-chip bf16 path every attention op of ``prefill_b*``,
    ``decode_chunk`` and ``mixed_chunk`` must be a Pallas kernel.
(b) runs one teacher-forced schedule — a prefill through every bucket
    (``last_only=True``, the branch every served prefill program
    takes), decode steps over the full batch, one mixed step — through
    up to three paths and compares LOGITS (never sampled streams:
    random-init weights put top-2 gaps inside bf16 rounding): the
    serving path, the pure-JAX bf16 path on one device
    (``LLMQ_PALLAS=0``'s routing) and a float32 ``jax.numpy`` run of the
    same weights. On a mesh the serving path is the GSPMD-partitioned
    pure-JAX program. With int8 WEIGHTS the kernels are gated apart
    from the network: see ``TOL_W8A8_RMS``.

Writes a JSON report to ``--out`` and exits non-zero when a check does
not hold. ``--tiny`` shrinks the model for the CPU unit test.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from typing import Any, Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: Max |Δlogit| allowed between two paths, on logits of std ≈ 1
#: (random-init). Two bf16 paths differ by reduction order, a bf16 path
#: and float32 by the rounding of every activation: measured on the v5e
#: at full-depth llama3-1b (PR 21) 0.078 kernel vs pure-JAX and 0.069 /
#: 0.070 kernel / pure-JAX vs float32, so twice that. A routing, layout
#: or sharding fault moves logits by O(1).
TOL = 0.15
#: int8 WEIGHTS (w8a8) quantize every activation dynamically, so a
#: bf16-rounding difference in one attention output can move the next
#: linear's input by a whole int8 step, and the MAX over 128k logits
#: is a heavy tail: measured at llama3-8b width (PR 21) max 0.24 / 0.33
#: kernel vs pure-JAX at 2 / 8 layers (0.34 at 32) while the RMS of
#: the same deltas is 0.042 / 0.047 on logits of RMS 1.0 — against max
#: 0.053 / 0.065 with bf16 weights and the SAME int8-KV kernel. A max
#: bound wide enough for that tail (0.7) sits next to the O(1) shift a
#: real fault makes, so with int8 weights the check has two parts:
#: the served w8a8 model is held to an RMS bound (twice the measured;
#: a routing or layout fault gives RMS ≈ 1.4), its max only reported;
#: and the KERNELS are gated at ``TOL`` on a model of the same width
#: and KV dtype with bf16 weights, depth cut to ``KERNEL_GATE_LAYERS``
#: so it fits beside nothing else (8B bf16 at full depth is 16 GB).
TOL_W8A8_RMS = 0.1
KERNEL_GATE_LAYERS = 8
#: float32 reference weights must fit beside the serving model.
F32_MAX_PARAMS = 2_000_000_000

MOSAIC_CALL = "tpu_custom_call"
#: Kernel function a route names (``ops/attention.kernel_routes``) ->
#: the instruction its Mosaic call is in a compiled program's text and a
#: device trace: the function ``ops/attention.py`` wraps in ``jax.jit``.
KERNEL_OPS = {
    "_kv_write_kernel": "kv_cache_write_pallas",
    "_kv_prefill_kernel": "kv_prefill_write_pallas",
    "_prefill_attn_kernel": "paged_prefill_attention_pallas",
    "_prefill_attn_kernel_q8": "paged_prefill_attention_q8_pallas",
    "_fused_kernel": "fused_decode_attention_pallas",
    "_fused_kernel_q8": "fused_decode_attention_q8_pallas",
}


class CheckFailure(AssertionError):
    pass


def check(cond: Any, msg: str) -> None:
    if not cond:
        raise CheckFailure(msg)


def say(msg: str) -> None:
    sys.stdout.write(f"[kernel_check] {msg}\n")
    sys.stdout.flush()


def check_program_text(ex, on_tpu: bool, expect_all_pallas: bool) -> Dict:
    """(a): compiled text vs logged routes, per warm-up program."""
    out = {}
    for name, compiled in sorted(ex._aot.items()):  # noqa: SLF001
        routes = ex.program_routes[name]
        kernels = sorted({impl.split(":", 1)[1]
                          for impl in routes.values()
                          if impl.startswith("pallas:")})
        text = compiled.as_text()
        n_calls = text.count(MOSAIC_CALL)
        out[name] = {"routes": routes, "mosaic_calls": n_calls}
        if on_tpu:
            if kernels:
                check(n_calls > 0,
                      f"{name}: routes name {kernels} but the compiled "
                      f"program has no {MOSAIC_CALL}")
                # ... and each named kernel's own call: a Mosaic call is
                # named after the jitted function that makes it.
                missing = [k for k in kernels if not re.search(
                    rf"%{KERNEL_OPS[k.split('(')[0]]}[. ]", text)]
                check(not missing,
                      f"{name}: routes name {missing} but the compiled "
                      f"program holds no call of theirs")
            else:
                check(n_calls == 0,
                      f"{name}: routes are all xla but the compiled "
                      f"program has {n_calls} {MOSAIC_CALL}")
        if expect_all_pallas and name.startswith(
                ("prefill_b", "decode_chunk", "mixed_chunk")):
            bad = {op: impl for op, impl in routes.items()
                   if not impl.startswith("pallas:")}
            check(not bad, f"{name}: expected every attention op on a "
                           f"Pallas kernel, got {bad}")
    return out


class Path:
    """One way of running the model: config + placed params + jitted
    prefill / decode / mixed steps that return logits."""

    def __init__(self, name: str, cfg, params, *, num_pages: int,
                 page_size: int, cache_dtype,
                 kv_shardings=None) -> None:
        import jax

        from llmq_tpu.models.llama import (forward_decode, forward_mixed,
                                           forward_prefill, init_kv_pages)

        self.name = name
        self.params = params
        def make():
            return init_kv_pages(cfg, num_pages, page_size,
                                 dtype=cache_dtype)

        def jit(f, n_out: int):
            """Donated cache; on a mesh, the executor's layout: the
            pool pinned on the way out of every program."""
            if kv_shardings is None:
                return jax.jit(f, donate_argnums=(1,))
            return jax.jit(
                f, donate_argnums=(1,),
                out_shardings=(None,) * n_out + (dict(kv_shardings),))

        # On a mesh the pool is created already sharded.
        self.cache = (make() if kv_shardings is None else
                      jax.jit(make, out_shardings=kv_shardings)())

        def prefill(params, cache, tokens, positions, lengths, bts):
            # last_only: what every served prefill program runs (the
            # row gather sits BEFORE the final norm and head), and no
            # (1, T, 128k) float32 logits to hold.
            logits, cache = forward_prefill(params, cfg, tokens, positions,
                                            lengths, cache, bts,
                                            last_only=True)
            return logits[0], cache

        def decode(params, cache, tokens, positions, bts, active):
            return forward_decode(params, cfg, tokens, positions, cache,
                                  bts, active=active)

        def mixed(params, cache, tokens, positions, bts, active,
                  pf_tokens, pf_positions, pf_lengths, pf_starts, pf_bts):
            return forward_mixed(
                params, cfg, tokens, positions, cache, bts, pf_tokens,
                pf_positions, pf_lengths, pf_starts, pf_bts,
                dec_active=active)

        self._prefill = jit(prefill, 1)
        self._decode = jit(decode, 1)
        self._mixed = jit(mixed, 2)

    def prefill(self, *a):
        out, self.cache = self._prefill(self.params, self.cache, *a)
        return out

    def decode(self, *a):
        out, self.cache = self._decode(self.params, self.cache, *a)
        return out

    def mixed(self, *a):
        dec, pf, self.cache = self._mixed(self.params, self.cache, *a)
        return dec, pf


def schedule(ex, seed: int = 0) -> Dict:
    """The teacher-forced inputs, from the executor's geometry alone:
    row r prefills a prompt that lands in bucket r (longest first, then
    short prompts), all rows decode ``N_DECODE`` forced steps, then one
    mixed step where the last S rows continue as prefill slices."""
    import numpy as np

    rng = np.random.default_rng(seed)
    spec = ex.spec
    B, ps, MP = spec.batch_size, spec.page_size, spec.max_pages_per_seq
    V = ex.model_cfg.vocab_size
    S = max(1, ex.mixed_prefill_slices)
    T = ex.mixed_slice_tokens or ex.prefill_buckets[0]
    n_decode = 3
    max_len = MP * ps
    buckets = sorted(ex.prefill_buckets, reverse=True)
    rows = []
    next_page = 1
    for r in range(B):
        slice_row = r >= B - S
        if r < len(buckets) and not slice_row:
            length = min(buckets[r], max_len) - 5
        else:
            length = 11 + (r % 7)
        # Room for the forced decode steps (+ the slice for slice rows).
        total = length + n_decode + 1 + (T if slice_row else 0)
        check(total <= max_len, f"row {r}: {total} tokens > {max_len}")
        n_pages = -(-total // ps)
        check(next_page + n_pages <= spec.num_pages,
              f"schedule needs more than {spec.num_pages} pages")
        bt = np.zeros(MP, np.int32)
        bt[:n_pages] = np.arange(next_page, next_page + n_pages)
        next_page += n_pages
        rows.append({"length": length, "bt": bt,
                     "prompt": rng.integers(3, V, length, dtype=np.int32)})
    return {
        "rows": rows, "S": S, "T": T, "n_decode": n_decode,
        "forced": rng.integers(3, V, (n_decode + 1, B), dtype=np.int32),
        "slices": rng.integers(3, V, (S, T), dtype=np.int32),
    }


def run_schedule(path: Path, ex, sch: Dict) -> Dict[str, Any]:
    """Run the schedule through one path; returns host float32 logits
    keyed by step name."""
    import jax.numpy as jnp
    import numpy as np

    spec = ex.spec
    B, MP = spec.batch_size, spec.max_pages_per_seq
    rows, S, T = sch["rows"], sch["S"], sch["T"]
    out: Dict[str, Any] = {}
    t0 = time.perf_counter()
    for r, row in enumerate(rows):
        L = row["length"]
        Tb = next(b for b in sorted(ex.prefill_buckets) if L <= b)
        toks = np.zeros((1, Tb), np.int32)
        toks[0, :L] = row["prompt"]
        pos = np.minimum(np.arange(Tb, dtype=np.int32), L - 1)[None]
        logits = path.prefill(jnp.asarray(toks), jnp.asarray(pos),
                              jnp.asarray([L], jnp.int32),
                              jnp.asarray(row["bt"])[None])
        out[f"prefill_b{Tb}.row{r}"] = np.asarray(logits, np.float32)
    bts = jnp.asarray(np.stack([row["bt"] for row in rows]))
    lens = np.asarray([row["length"] for row in rows], np.int32)
    all_on = jnp.ones(B, bool)
    for j in range(sch["n_decode"]):
        logits = path.decode(jnp.asarray(sch["forced"][j]),
                             jnp.asarray(lens + j), bts, all_on)
        out[f"decode.step{j}"] = np.asarray(logits, np.float32)
    # Mixed step: rows < B-S decode one more forced token; rows >= B-S
    # continue as T-token prefill slices over their own pages.
    j = sch["n_decode"]
    pos_now = lens + j
    active = np.arange(B) < B - S
    sl = list(range(B - S, B))
    pf_pos = np.stack([pos_now[r] + np.arange(T, dtype=np.int32)
                       for r in sl])
    pf_bts = jnp.asarray(np.stack([rows[r]["bt"] for r in sl]))
    from llmq_tpu.ops.rows import pack_grid
    tight_tok, tight_pos, starts = pack_grid(sch["slices"], pf_pos, [T] * S)
    dec, pf = path.mixed(
        jnp.asarray(sch["forced"][j]), jnp.asarray(pos_now), bts,
        jnp.asarray(active), jnp.asarray(tight_tok), jnp.asarray(tight_pos),
        jnp.full((S,), T, jnp.int32), jnp.asarray(starts), pf_bts)
    out["mixed.decode"] = np.asarray(dec, np.float32)[:B - S]
    out["mixed.slices"] = np.asarray(pf, np.float32)
    say(f"path {path.name}: schedule ran in "
        f"{time.perf_counter() - t0:.1f}s")
    return out


def compare(a: Dict, b: Dict, tol: float | None, label: str,
            rms_tol: float | None = None) -> Dict:
    """Hold two paths' logits together: max |Δ| within ``tol`` and/or
    RMS Δ within ``rms_tol`` (``None`` = reported, not gated)."""
    import numpy as np

    worst: Tuple[float, str] = (0.0, "")
    per_step = {}
    agree = total = 0
    sq = n = 0.0
    for key in a:
        check(np.isfinite(a[key]).all() and np.isfinite(b[key]).all(),
              f"{label}: non-finite logits at {key}")
        check(a[key].shape == b[key].shape,
              f"{label}: shape {a[key].shape} vs {b[key].shape} at {key}")
        delta = a[key] - b[key]
        d = float(np.abs(delta).max())
        sq += float(np.square(delta, dtype=np.float64).sum())
        n += delta.size
        group = key.split(".")[0]
        per_step[group] = max(per_step.get(group, 0.0), d)
        if d > worst[0]:
            worst = (d, key)
        x = a[key].reshape(-1, a[key].shape[-1])
        y = b[key].reshape(-1, b[key].shape[-1])
        agree += int((x.argmax(-1) == y.argmax(-1)).sum())
        total += x.shape[0]
    rms = (sq / max(n, 1.0)) ** 0.5
    res = {"max_abs_delta": round(worst[0], 4), "at": worst[1],
           "tolerance": tol, "rms_delta": round(rms, 5),
           "rms_tolerance": rms_tol, "argmax_agree": f"{agree}/{total}",
           "by_program": {k: round(v, 4) for k, v in per_step.items()}}
    say(f"{label}: {json.dumps(res)}")
    check(tol is None or worst[0] <= tol,
          f"{label}: max |dlogit| {worst[0]:.4f} at {worst[1]} exceeds "
          f"tolerance {tol}")
    check(rms_tol is None or rms <= rms_tol,
          f"{label}: RMS dlogit {rms:.4f} exceeds tolerance {rms_tol}")
    return res


def register_model_file(path: str) -> Dict[str, Any]:
    """Register the model of a benchmark configuration file, as the
    benchmark's own child does, and return the file's ``server`` block,
    which is a configuration file of the program."""
    from benchmark.harness import contract
    from benchmark.harness.child import register_model

    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    register_model(doc["server"]["model"]["name"],
                   {k: doc[k] for k in contract.MODEL_KEYS if k in doc})
    return doc["server"]


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="")
    ap.add_argument("--model-file", default="",
                    help="a benchmark configuration (benchmark/configs/"
                         "*.json): check its model at its server geometry")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU unit test: keep the configured (tiny) "
                         "model and skip the TPU-only assertions")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.core.config import load_config
    from llmq_tpu.engine import build_engine
    from llmq_tpu.observability.device import device_identity

    ident = device_identity()
    on_tpu = ident["platform"] == "tpu"
    check(on_tpu or args.tiny,
          f"kernel phase came up on {ident}, not a TPU")
    if args.model_file:
        import tempfile
        with tempfile.NamedTemporaryFile("w", suffix=".json") as f:
            json.dump(register_model_file(args.model_file), f)  # is YAML
            f.flush()
            cfg = load_config(f.name)
    else:
        cfg = load_config()
    cfg.executor.backend = "jax"
    t0 = time.perf_counter()
    engine = build_engine(cfg, warmup=True)
    ex = engine.executor
    mcfg = ex.model_cfg
    from importlib import metadata

    def version(dist: str) -> str:
        try:
            return metadata.version(dist)
        except metadata.PackageNotFoundError:
            return "not installed"

    report: Dict[str, Any] = {
        "device": ident,
        "versions": {d: version(d) for d in ("jax", "jaxlib", "libtpu")},
        "model": mcfg.name, "n_layers": mcfg.n_layers,
        "geometry": {"batch": ex.spec.batch_size,
                     "page_size": ex.spec.page_size,
                     "num_pages": ex.spec.num_pages,
                     "max_pages_per_seq": ex.spec.max_pages_per_seq,
                     "prefill_buckets": ex.prefill_buckets,
                     "chunk": ex.chunk_size,
                     "mixed": [ex.mixed_prefill_slices,
                               ex.mixed_slice_tokens],
                     "mesh": (dict(ex.mesh.shape) if ex.mesh is not None
                              else None)},
        "engine_build_s": round(time.perf_counter() - t0, 1),
        "hbm_chips": ex.hbm_info(),
    }
    quant_w = cfg.model.quantization == "int8"
    quant_kv = "k_scale" in ex.cache
    shipped_path = ex.mesh is None and not quant_kv
    def write_report() -> None:
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1, sort_keys=True)

    report["programs"] = check_program_text(
        ex, on_tpu, expect_all_pallas=on_tpu and shipped_path)
    write_report()
    say(f"program text ok: " + json.dumps(
        {n: p["mosaic_calls"] for n, p in report["programs"].items()}))

    # (b) teacher-forced logits. Free the engine's pool first; its
    # params are the serving path's params (same placement).
    sch = schedule(ex)
    cache_dtype = jnp.int8 if quant_kv else None
    geom = dict(num_pages=ex.spec.num_pages, page_size=ex.spec.page_size)
    kv_shd = ex._kv_shardings  # noqa: SLF001
    serving_params = ex.params
    engine.stop()
    ex.cache = None
    ex._aot.clear()  # noqa: SLF001

    serving = run_schedule(
        Path("serving", mcfg, serving_params, cache_dtype=cache_dtype,
             kv_shardings=kv_shd, **geom), ex, sch)
    results = {}
    pure_cfg = dataclasses.replace(mcfg, pallas=False)
    one_dev = jax.devices()[0]
    pure_params = (serving_params if ex.mesh is None else jax.tree.map(
        lambda x: jax.device_put(np.asarray(x), one_dev), serving_params))
    pure = run_schedule(
        Path("pure_bf16", pure_cfg, pure_params, cache_dtype=cache_dtype,
             **geom), ex, sch)
    if quant_w:
        results["serving_vs_pure"] = compare(
            serving, pure, None, "serving vs pure-JAX (w8a8)",
            rms_tol=TOL_W8A8_RMS)
    else:
        results["serving_vs_pure"] = compare(serving, pure, TOL,
                                             "serving vs pure-JAX")
    del pure_params
    n_params = sum(int(x.size) for x in jax.tree.leaves(serving_params))
    if quant_w and ex.mesh is None:
        # The kernel gate proper: same width, KV dtype and geometry,
        # bf16 weights, depth cut to fit (see TOL_W8A8_RMS).
        from llmq_tpu.models.llama import init_params

        del serving_params
        ex.params = None
        gate_cfg = dataclasses.replace(
            mcfg, n_layers=min(mcfg.n_layers, KERNEL_GATE_LAYERS))
        gate_params = init_params(jax.random.PRNGKey(0), gate_cfg)
        gate = [run_schedule(
            Path(name, c, gate_params, cache_dtype=cache_dtype, **geom),
            ex, sch) for name, c in (
                ("kernels_bf16w", gate_cfg),
                ("pure_bf16w", dataclasses.replace(gate_cfg,
                                                   pallas=False)))]
        results["kernels_vs_pure_bf16_weights"] = dict(
            compare(gate[0], gate[1], TOL,
                    "kernels vs pure-JAX (bf16 weights)"),
            n_layers=gate_cfg.n_layers)
        del gate_params
    if quant_w or quant_kv or n_params > F32_MAX_PARAMS:
        # A float32 run of int8 weights would also measure activation
        # quantization, and 8B float32 does not fit beside the model.
        results["serving_vs_f32"] = "not run (int8 or too large for f32)"
        say("float32 reference: " + results["serving_vs_f32"])
    else:
        f32_cfg = dataclasses.replace(mcfg, pallas=False,
                                      dtype=jnp.float32)
        f32_params = jax.tree.map(
            lambda x: jax.device_put(np.asarray(x).astype(np.float32),
                                     one_dev), serving_params)
        del serving_params
        ex.params = None
        # TPU matmuls round float32 operands to bf16 passes unless
        # asked not to: without this the "float32" run is not one.
        with jax.default_matmul_precision("float32"):
            f32 = run_schedule(
                Path("f32", f32_cfg, f32_params, cache_dtype=jnp.float32,
                     **geom), ex, sch)
        results["serving_vs_f32"] = compare(serving, f32, TOL,
                                            "serving vs float32")
        results["pure_vs_f32"] = compare(pure, f32, TOL,
                                         "pure-JAX vs float32")
    report["logits"] = results
    report["summary"] = {
        "model": mcfg.name, "mesh": report["geometry"]["mesh"],
        "versions": report["versions"],
        "mosaic_calls": {n: p["mosaic_calls"]
                         for n, p in report["programs"].items()},
        "max_abs_delta": {k: (v["max_abs_delta"] if isinstance(v, dict)
                              else v) for k, v in results.items()}}
    write_report()
    say("ok " + json.dumps(report["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
