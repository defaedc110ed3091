"""The server child: the only process of a run that touches JAX.

Started by ``benchmark/run.py`` with a spec file. It

1. fails unless JAX reports the platform and the number of chips the
   cell asks for (no fallback);
2. loads the model family the cell's configuration names
   (``<path>/families/<family>/``, found by ``contract``) and has its
   ``adapter.py`` register the configuration (``configs/*.json``) with
   the program — the program is not edited, and this file names no
   model;
3. makes the weights on the device from ``--seed`` in one jitted call
   of the adapter's builder, in the type they are served in;
4. holds logits of the serving path's own model functions (the
   adapter's: kernels, paged cache) against the family's plain float32
   reference (``reference.py``) on a seeded sample — before the KV pool
   exists, so the float32 copy of a layer has room;
5. builds the program's own ``App`` (API server, queue plane, workers,
   engine) from the configuration's ``server`` block and serves on
   ``127.0.0.1``;
6. taps every request the engine is given with ``GenHandle.on_token``,
   the program's public streaming callback, and stamps the host's clock
   there: first token, last token, count. Those stamps are the
   benchmark's own, not marks read from the program;
7. obeys one-line JSON commands on stdin and answers on the reply pipe:
   ``mark`` (clock, token count, engine counters), ``trace`` (a profiler
   capture, reduced here), ``drain``, ``dump``, ``watch``, ``quit``.

Nothing here polls per request while a window is open.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class Reply:
    """One JSON object per line on the reply pipe; events and answers
    share it, so writes are locked."""

    def __init__(self, fd: int) -> None:
        self._f = os.fdopen(fd, "w", buffering=1)
        self._mu = threading.Lock()

    def send(self, obj: Dict[str, Any]) -> None:
        line = json.dumps(obj)
        with self._mu:
            self._f.write(line + "\n")
            self._f.flush()


# -- weights -------------------------------------------------------------------


def make_params(seed: int, build):
    """The family's ``build(key)`` as ONE jitted call on the device."""
    import jax
    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    params = jax.jit(build)(key)
    jax.block_until_ready(params)
    return params


def __getattr__(name: str) -> Any:
    # The import path that is older than families: see
    # ``contract.first_family``.
    if name == "register_model":
        from benchmark.harness import contract
        return contract.first_family("adapter").register
    raise AttributeError(name)


# -- correctness ---------------------------------------------------------------


def source_digest() -> str:
    """Digest of the program's model and kernel sources and of the JAX
    in use: a check program traced from other code must never be found
    in the cache."""
    import hashlib

    import jax
    h = hashlib.sha256(jax.__version__.encode())
    for sub in ("models", "ops"):
        base = os.path.join(ROOT, "llmq_tpu", sub)
        for d, _dirs, files in sorted(os.walk(base)):
            for name in sorted(files):
                if name.endswith(".py"):
                    h.update(name.encode())
                    with open(os.path.join(d, name), "rb") as f:
                        h.update(f.read())
    return h.hexdigest()


def exported(name: str, fn, args, ident: str):
    """``fn`` traced and lowered once per checkout: the lowered program
    (``jax.export``, Mosaic payloads included) is kept beside the
    compile cache under a key of its argument shapes, ``ident`` and
    ``source_digest()``, so a warm run neither traces nor lowers it
    again (some seconds a program at 24-32 unrolled layers). The XLA
    compilation itself is the persistent cache's."""
    import hashlib

    import jax
    from jax import export

    shapes = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), args)
    key = hashlib.sha256(repr((name, ident, shapes, source_digest(),
                               jax.devices()[0].device_kind)
                              ).encode()).hexdigest()[:32]
    cache = jax.config.jax_compilation_cache_dir
    path = (os.path.join(cache, "bench_check", f"{name}-{key}.jaxexp")
            if cache else None)
    if path and os.path.exists(path):
        with open(path, "rb") as f:
            return export.deserialize(bytearray(f.read())).call
    specs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
    exp = export.export(jax.jit(fn))(*specs)
    if path:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(exp.serialize())
        os.replace(tmp, path)
    return exp.call



def check_logits(params, path, reference_logits,
                 spec: Dict[str, Any]) -> Dict[str, Any]:
    """Teacher-forced logits of the serving path (``path``: the
    family's ``adapter.serving_path`` — the program's own prefill of the
    last position through the cell's smallest bucket, then decode steps
    through the paged cache, with the kernels the served programs route
    to) against the family's float32 reference's full forward pass. A
    small pool of its own: the server's pool does not exist yet."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ex = spec["config"]["server"]["executor"]
    tol = spec["config"]["tolerance"]
    ps = int(ex["page_size"])
    bucket = int(min(ex["prefill_buckets"]))
    n_dec, rows_b = 3, 8
    rng = np.random.default_rng(spec["seed"] % (2 ** 31))
    lengths = [bucket - 5, max(8, bucket // 3)]
    max_pages = -(-(bucket + n_dec + 1) // ps)
    cache = path.cache(1 + len(lengths) * max_pages)

    t_mark = [time.perf_counter()]
    phases: Dict[str, float] = {}

    def lap(name: str) -> None:
        now = time.perf_counter()
        phases[name] = round(phases.get(name, 0.0) + now - t_mark[0], 3)
        t_mark[0] = now

    # The traced functions keep these names whatever the family: they
    # name the compiled programs, so XLA's cache finds them again.
    def prefill_fn(params, cache, tokens, positions, lens, bts):
        return path.prefill(params, cache, tokens, positions, lens, bts)

    def decode_fn(params, cache, tokens, positions, bts, active):
        return path.decode(params, cache, tokens, positions, bts, active)

    i32 = jnp.int32
    prefill = exported("check_prefill", prefill_fn, (
        params, cache, jax.ShapeDtypeStruct((1, bucket), i32),
        jax.ShapeDtypeStruct((1, bucket), i32),
        jax.ShapeDtypeStruct((1,), i32),
        jax.ShapeDtypeStruct((1, max_pages), i32)), path.ident)
    decode = exported("check_decode", decode_fn, (
        params, cache, jax.ShapeDtypeStruct((rows_b,), i32),
        jax.ShapeDtypeStruct((rows_b,), i32),
        jax.ShapeDtypeStruct((rows_b, max_pages), i32),
        jax.ShapeDtypeStruct((rows_b,), jnp.bool_)), path.ident)

    lap("programs")
    bts = np.zeros((rows_b, max_pages), np.int32)
    seqs, served = [], [[] for _ in lengths]
    for r, n in enumerate(lengths):
        bts[r] = 1 + r * max_pages + np.arange(max_pages)
        seqs.append(rng.integers(3, path.vocab_size, n + n_dec,
                                 dtype=np.int32))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = seqs[r][:n]
        pos = np.minimum(np.arange(bucket, dtype=np.int32), n - 1)[None]
        logits, cache = prefill(params, cache, jnp.asarray(toks),
                                jnp.asarray(pos),
                                jnp.asarray([n], jnp.int32),
                                jnp.asarray(bts[r:r + 1]))
        served[r].append(np.asarray(logits, np.float32)[0])
    lap("prefill")
    active = np.arange(rows_b) < len(lengths)
    for j in range(n_dec):
        tok = np.zeros(rows_b, np.int32)
        pos = np.zeros(rows_b, np.int32)
        for r, n in enumerate(lengths):
            tok[r], pos[r] = seqs[r][n + j], n + j
        logits, cache = decode(params, cache, jnp.asarray(tok),
                               jnp.asarray(pos), jnp.asarray(bts),
                               jnp.asarray(active))
        out = np.asarray(logits, np.float32)
        for r in range(len(lengths)):
            served[r].append(out[r])
    del cache
    lap("decode")
    worst_max, worst_rms, ref_rms = 0.0, 0.0, 0.0
    for r, n in enumerate(lengths):
        ref = np.asarray(reference_logits(
            params, seqs[r], spec["config"]["model"],
            list(range(n - 1, n + n_dec))))
        d = np.stack(served[r]) - ref
        worst_max = max(worst_max, float(np.abs(d).max()))
        worst_rms = max(worst_rms, float(np.sqrt((d * d).mean(-1)).max()))
        ref_rms = max(ref_rms, float(np.sqrt((ref * ref).mean())))
    lap("reference")
    ok = worst_rms <= tol["rms"] and (
        tol.get("max") is None or worst_max <= tol["max"])
    return {"ok": bool(ok), "max_abs": worst_max, "rms": worst_rms,
            "reference_rms": ref_rms, "tolerance": tol, "phases_s": phases,
            "positions": len(lengths) * (n_dec + 1)}


# -- the tap -------------------------------------------------------------------


class Tap:
    """The benchmark's own clock on every request the engine is given."""

    def __init__(self, reply: Reply) -> None:
        self.reply = reply
        self.recs: Dict[str, List[Any]] = {}
        self.handles: Dict[str, Any] = {}
        self.tokens = 0
        self.watching = False
        #: conversation -> tokens of context after its newest turn, by
        #: the benchmark's own count of what it sent and asked for.
        self.conv_tokens: Dict[str, int] = {}

    def install(self, engine) -> None:
        inner = engine.submit
        tokenizer = engine.tokenizer

        def submit(req):
            handle = inner(req)
            # [t_submit, t_first, t_last, n, max_new, context_before]
            conv = req.conversation_id or ""
            n_prompt = len(tokenizer.encode(req.prompt))
            rec = [time.perf_counter(), None, None, 0,
                   int(req.max_new_tokens or 0),
                   self.conv_tokens.get(conv, 0) + n_prompt]
            if conv:
                self.conv_tokens[conv] = rec[5] + rec[4]
            self.recs[req.id] = rec
            self.handles[req.id] = handle
            rid = req.id

            def on_token(_tok: int) -> None:
                now = time.perf_counter()
                if rec[3] == 0:
                    rec[1] = now
                rec[2] = now
                rec[3] += 1
                self.tokens += 1
                if rec[3] == rec[4] and self.watching:
                    self.reply.send({"event": "done", "id": rid})

            handle.on_token(on_token)
            return handle

        engine.submit = submit

    def snapshot(self) -> Dict[str, List[Any]]:
        """Every stream that has begun and not ended: its last stamp
        and its count, as of now."""
        return {rid: [r[1], r[2], r[3]] for rid, r in
                list(self.recs.items())
                if r[3] > 0 and not self.handles[rid].wait(0)}

    def load(self) -> Dict[str, float]:
        """Rows decoding now and the context tokens they attend to."""
        rows, ctx = 0, 0
        for rid, r in list(self.recs.items()):
            if 0 < r[3] < r[4] and not self.handles[rid].wait(0):
                rows += 1
                ctx += r[5] + r[3]
        return {"t": time.perf_counter(), "rows": rows,
                "context_tokens": ctx}

    def sweep(self) -> None:
        """Report streams that ended short of their length (EOS, error):
        the closed loop's clients wait for a ``done``. Rare, so a slow
        sweep is enough."""
        seen = set()
        while True:
            time.sleep(0.5)
            if not self.watching:
                continue
            for rid, h in list(self.handles.items()):
                rec = self.recs[rid]
                if rid not in seen and rec[3] != rec[4] and h.wait(0):
                    seen.add(rid)
                    self.reply.send({"event": "done", "id": rid})


# -- the process ---------------------------------------------------------------


def device_block() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    ap.add_argument("--reply-fd", type=int, required=True)
    args = ap.parse_args(argv)
    with open(args.spec, "r", encoding="utf-8") as f:
        spec = json.load(f)
    reply = Reply(args.reply_fd)
    sys.path.insert(0, ROOT)
    stages: Dict[str, float] = {}

    import jax
    dev = device_block()
    if dev["platform"] != spec["platform"] or dev["count"] < spec["chips"]:
        sys.stderr.write(f"need {spec['chips']} {spec['platform']} chip(s), "
                         f"JAX reports {dev}\n")
        return 3
    stages["jax_start"] = time.perf_counter() - T_START

    from llmq_tpu.parallel import enable_compilation_cache
    enable_compilation_cache()

    from benchmark.harness import contract, tracered
    config = spec["config"]
    srv = config["server"]
    adapter = contract.load_family(spec["family_dir"], "adapter")
    kernels = contract.load_family(spec["family_dir"], "shapes")
    t0 = time.perf_counter()
    mcfg = adapter.register(srv["model"]["name"], config)
    params = make_params(spec["seed"],
                         adapter.param_builder(mcfg, srv["model"]))
    stages["weights"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    correctness = check_logits(
        params, adapter.serving_path(mcfg, srv),
        contract.load_family(spec["family_dir"],
                             "reference").reference_logits, spec)
    stages["correctness"] = time.perf_counter() - t0

    # The program's own wiring, as ``python -m llmq_tpu serve`` does it.
    t0 = time.perf_counter()
    os.makedirs(spec["workdir"], exist_ok=True)
    cfg_path = os.path.join(spec["workdir"], "server.json")
    with open(cfg_path, "w", encoding="utf-8") as f:
        json.dump(srv, f)          # JSON is YAML
    from llmq_tpu import chaos, observability, tenancy
    from llmq_tpu.core.config import load_config
    from llmq_tpu.utils.logging import configure_logging
    cfg = load_config(cfg_path, env=False)
    cfg.server.host = "127.0.0.1"
    cfg.server.port = int(spec["port"])
    cfg.executor.backend = "jax"
    configure_logging(cfg.logging.level, cfg.logging.format,
                      cfg.logging.output)
    observability.configure(cfg.observability)
    chaos.configure(cfg.chaos)
    tenancy.configure_tenancy(cfg.tenancy)

    import functools

    import llmq_tpu.engine as engine_pkg
    from llmq_tpu.__main__ import App
    engine_pkg.build_engine = functools.partial(engine_pkg.build_engine,
                                                params=params)
    app = App(cfg, with_api=True, with_workers=True, with_engine=True,
              with_scheduler=True)
    tap = Tap(reply)
    tap.install(app.engine)
    app.start()
    threading.Thread(target=tap.sweep, name="bench-sweep",
                     daemon=True).start()
    stages["engine"] = time.perf_counter() - t0
    programs = {}
    try:
        comp = app.engine.get_stats()["device"]["compile"]
        programs = {k: v.get("source") for k, v in comp["programs"].items()}
    except (KeyError, AttributeError, TypeError):
        pass
    reply.send({"event": "ready", "device": dev, "stages_s": stages,
                "correctness": correctness, "programs": programs,
                "hbm": app.engine.executor.hbm_info(),
                "pid": os.getpid()})

    def counters(streams: bool = True) -> Dict[str, Any]:
        st = app.engine.get_stats()
        out = {"t": time.perf_counter(), "tokens": tap.tokens,
               "decode_steps": st.get("decode_steps"),
               "tokens_generated": st.get("tokens_generated"),
               "active": st.get("active"), "pending": st.get("pending"),
               "kv_pages_used": st.get("kv_pages_used"),
               "kv_pages_total": st.get("kv_pages_total"),
               "backend_compiles": (st.get("device", {}).get("compile", {})
                                    .get("backend_compiles")),
               "mixed_steps": (st.get("mixed_batch") or {}).get("steps"),
               "prefix_cache": st.get("prefix_cache")}
        if streams:
            out["streams"] = tap.snapshot()
        return out

    captures: List[Dict[str, Any]] = []

    def do_trace(cmd: Dict[str, Any]) -> Dict[str, Any]:
        """One profiler capture, held for ``seconds``. While it is held
        the tap is sampled a few times a second (rows decoding, tokens
        of context they attend to): what the roofline shares need. The
        capture is reduced later, at ``dump``, after the window."""
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = int(cmd.get("python", 1))
        opts.host_tracer_level = 2
        t_a = time.perf_counter()
        before = counters(streams=False)
        jax.profiler.start_trace(cmd["dir"], profiler_options=opts)
        t_b = time.perf_counter()
        samples = []
        while time.perf_counter() - t_b < float(cmd["seconds"]):
            samples.append(tap.load())
            time.sleep(0.2)
        after = counters(streams=False)
        t_c = time.perf_counter()
        jax.profiler.stop_trace()
        cap = {"dir": cmd["dir"], "python": opts.python_tracer_level,
               "start_s": t_b - t_a, "held_s": t_c - t_b,
               "stop_s": time.perf_counter() - t_c,
               "t_begin": t_b, "t_end": t_c, "before": before,
               "after": after, "samples": samples}
        captures.append(cap)
        return {"held_s": cap["held_s"], "stop_s": cap["stop_s"]}

    def do_drain(cmd: Dict[str, Any]) -> Dict[str, Any]:
        """Wait, after the window, until the listed requests ended. One
        that never reached the engine (still queued) is unfinished."""
        deadline = time.perf_counter() + float(cmd["limit_s"])
        left = list(cmd["ids"])
        while True:
            left = [i for i in left if i not in tap.handles
                    or not tap.handles[i].wait(0)]
            if not left or time.perf_counter() >= deadline:
                return {"unfinished": left}
            time.sleep(0.02)

    def do_dump(cmd: Dict[str, Any]) -> Dict[str, Any]:
        rec = observability.get_recorder()
        anchor = time.time() - time.perf_counter()
        timelines = {}
        for tl in rec.recent(rec.capacity):
            stages_: Dict[str, float] = {}
            meta: Dict[str, Any] = {}
            for e in tl.events:
                stages_.setdefault(e.stage, e.ts - anchor)
                if e.stage in ("completed", "failed", "cancelled"):
                    # The engine and the worker each stamp a terminal
                    # event; the engine's carries the request's counts.
                    meta.update({k: e.meta[k] for k in (
                        "finish_reason", "completion_tokens",
                        "prompt_tokens", "cached_tokens", "priority",
                        "decode_device_s") if e.meta.get(k) is not None})
                    if meta.get("terminal") != "failed":
                        meta["terminal"] = e.stage
            timelines[tl.request_id] = {"stages": stages_, "meta": meta}
        for cap in captures:
            t_r = time.perf_counter()
            cap["reduced"] = tracered.reduce_dir(
                cap["dir"], kernels.DECODE_ATTN, kernels.PREFILL_ATTN)
            cap["reduce_s"] = time.perf_counter() - t_r
        out = {"taps": {rid: r for rid, r in tap.recs.items()},
               "captures": captures,
               "timelines": timelines,
               "recorder": {"capacity": rec.capacity,
                            "dropped": rec.dropped},
               "memory_peak_bytes": memory_peak(),
               "counters": counters()}
        with open(cmd["out"], "w", encoding="utf-8") as f:
            json.dump(out, f)
        return {"out": cmd["out"]}

    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        cmd = json.loads(line)
        op = cmd.get("op")
        ans: Dict[str, Any] = {"re": cmd.get("seq")}
        if op == "mark":
            ans.update(counters())
        elif op == "watch":
            tap.watching = bool(cmd.get("on", True))
        elif op == "trace":
            ans.update(do_trace(cmd))
        elif op == "drain":
            ans.update(do_drain(cmd))
        elif op == "dump":
            ans.update(do_dump(cmd))
        elif op == "quit":
            reply.send(ans)
            break
        else:
            ans["error"] = f"unknown op {op!r}"
        reply.send(ans)
    app.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
