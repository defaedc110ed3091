"""The device's time under the program's own names: a capture's
optimised HLO joined to its device events.

The traced programs name their own parts with ``jax.named_scope``
(``llmq_tpu/utils/profiling.py`` ``SCOPES``: the chunk programs' steps,
the row kinds of a mixed step, the model's modules). The profiler's
device events do not carry those names; the capture's ``/host:metadata``
plane holds each module's optimised HLO, whose instructions carry them
in ``op_name``. ``tracered.py`` sorts operation names the compiler
chose (``fusion``, ``copy``); this module sorts the same self times by
what the PROGRAM called the work.

``xplane_to_neutral`` turns the FIRST capture (Python tracer off) into
a plain form —

    {"vocabulary": [names], "modules": {module: {instruction: op_name}},
     "planes": [{"name", "t0_ns", "lo_ns", "hi_ns",
                 "names": [instruction names],
                 "runs": [[module, start_ns, dur_ns], ...],
                 "ops": [[name index, start_ns, dur_ns, run index], ...]}]}

— of the device planes' ``XLA Modules`` runs, their ``XLA Ops`` events
with the run that holds each (-1: none), the capture's edges on that
plane (``lo_ns``, ``hi_ns``: its first operation's start and its last
one's end; times count from ``t0_ns``), and, of every module that ran,
the ``op_name`` of each instruction that has an event. The HLO is read
with a protobuf wire decoder of this file's own (standard library: the
chip machines' TensorFlow takes nine seconds to import, and nothing
else there parses an ``XSpace``'s metadata); the events with
``jax.profiler.ProfileData``, as ``spans.py`` reads them, which is the
only part that needs JAX: ``neutral_of`` runs this file as a subprocess
(``JAX_PLATFORMS=cpu``) and keeps the result beside the trace, one
parse for all readers. The vocabulary is the PROGRAM's (imported from
the checkout the capture was made in): a program without one (the
parent of the PR that added it) names nothing and reads as ``None``.

``reduce_neutral`` is standard library alone
(``selftest/test_scopes.py``). Its rules:

- An operation's time is its SELF time (``tracered.self_times``' rule:
  its children's intervals taken out), so a ``while`` is not counted on
  top of its body; what a ``while`` keeps is the loop's own overhead.
- An operation's **scope path** is the components of its instruction's
  ``op_name`` that are in the vocabulary, in order, joined by ``/``
  (``jit(...)``, ``while``, ``body``, transforms and primitive names
  dropped). An instruction with none takes the path of the event it
  runs inside (a copy the compiler put into a loop's body has no
  ``op_name``; the ``while`` around it has), and is ``(unscoped)``
  where it runs inside none.
- A fusion goes WHOLE to the path its own instruction carries. XLA may
  fuse across a scope's boundary (a norm into the product before it):
  the fused instruction has one ``op_name``, and all of its time goes
  there.
- Only program runs that lie whole inside the capture are counted: a
  run the capture's edges cut would count a mixed step without its
  loop. The profiler CLIPS a cut run's event to the capture (measured,
  PR 36: the first run of a capture begins 5 ns before its first
  operation, the last ends 3 ns after its last), so a cut run cannot be
  told from a whole one that happens to stand at the edge: every run
  that touches ``lo_ns`` or ``hi_ns`` is left out.

Conservation: the paths and ``(unscoped)`` add up to ``tracered``'s
operation self times over the same whole runs.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import subprocess
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple

if __name__ == "__main__":       # run as a file: find the package
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.harness.tracered import (DEVICE_PLANE, MODULES_LINE,  # noqa: E402
                                        OPS_LINE, find_xplane, op_name)

NEUTRAL_FILE = "scopes_neutral.json"
UNSCOPED = "(unscoped)"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
#: A run touches an edge of the capture when it begins within this of
#: the capture's first operation, or ends within this of its last (a
#: clipped run's event begins 5 ns before its first operation, a whole
#: one's some 300 ns).
EDGE_NS = 1000.0
#: The step level of the vocabulary, and the modules whose time is a
#: dense product's: what the metric files ask for by name.
MIXED, LOOP, DECODE_ROWS = "mixed_step", "decode_loop", "decode_rows"
SLICES_DENSE = ("qkv", "attn_out", "mlp", "moe_experts", "head")
DECODE_DENSE = ("qkv", "attn_out", "mlp", "head")

# -- protobuf wire format (standard library) -----------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes, lo: int = 0,
           hi: Optional[int] = None) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of the message in ``buf[lo:hi]``: an int
    for a varint, the ``(lo, hi)`` span of a length-delimited field,
    ``None`` for a fixed-width one (none is asked for here)."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf: bytes, lo: int, hi: int,
                 number: int) -> Iterator[Tuple[int, int]]:
    """The value spans of a ``map<int64, Message>`` field."""
    for num, span in fields(buf, lo, hi):
        if num == number:
            for k, v in fields(buf, *span):
                if k == 2:
                    yield v


def instruction_names(buf: bytes, span: Tuple[int, int]) -> Dict[str, str]:
    """``HloProto`` -> instruction name -> ``op_name`` (its
    ``OpMetadata``), over every computation of the module."""
    out: Dict[str, str] = {}
    for num, module in fields(buf, *span):
        if num != 1:                           # HloProto.hlo_module
            continue
        for num_c, comp in fields(buf, *module):
            if num_c != 3:                     # HloModuleProto.computations
                continue
            for num_i, ins in fields(buf, *comp):
                if num_i != 2:                 # .instructions
                    continue
                name, scope = "", ""
                for k, v in fields(buf, *ins):
                    if k == 1:                 # HloInstructionProto.name
                        name = _text(buf, v)
                    elif k == 7:               # .metadata
                        for km, vm in fields(buf, *v):
                            if km == 2:        # OpMetadata.op_name
                                scope = _text(buf, vm)
                if name:
                    out[name] = scope
    return out


def hlo_modules(buf: bytes) -> Dict[str, Dict[str, str]]:
    """``XSpace`` bytes -> module name (``jit_mixed_chunk(<id>)``, as
    on the ``XLA Modules`` line) -> ``instruction_names`` of its
    optimised HLO, from the ``/host:metadata`` plane."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in fields(buf):
        if num != 1:                           # XSpace.planes
            continue
        top = list(fields(buf, *plane))
        if not any(k == 2 and _text(buf, v) == METADATA_PLANE
                   for k, v in top):
            continue
        hlo_stat = None
        for meta in _map_entries(buf, plane[0], plane[1], 5):
            ident, name = 0, ""                # XStatMetadata
            for k, v in fields(buf, *meta):
                if k == 1:
                    ident = v
                elif k == 2:
                    name = _text(buf, v)
            if name == HLO_STAT:
                hlo_stat = ident
        for meta in _map_entries(buf, plane[0], plane[1], 4):
            name, proto = "", None             # XEventMetadata
            for k, v in fields(buf, *meta):
                if k == 2:
                    name = _text(buf, v)
                elif k == 5:                   # .stats: XStat
                    stat = dict((ks, vs) for ks, vs in fields(buf, *v)
                                if ks in (1, 6))
                    if stat.get(1) == hlo_stat and 6 in stat:
                        proto = stat[6]
            if name and proto is not None:
                out[name] = instruction_names(buf, proto)
    return out


# -- .xplane.pb -> neutral (needs JAX for the events) ----------------------------


def instruction_of(raw: str) -> str:
    """An ``XLA Ops`` event's name -> its HLO instruction's:
    ``%fusion.6066 = bf16[...] fusion(...)`` -> ``fusion.6066``."""
    return raw.split(" = ", 1)[0].strip().lstrip("%")


def program_vocabulary() -> List[str]:
    """``SCOPES`` of the program in this checkout; none where it has
    none."""
    try:
        from llmq_tpu.utils.profiling import SCOPES
        return list(SCOPES)
    except Exception:                          # the parent: no vocabulary
        return []


def xplane_to_neutral(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    hlo = hlo_modules(raw)
    by_stem: Dict[str, List[str]] = {}
    for name in hlo:
        by_stem.setdefault(name.split("(")[0], []).append(name)
    planes, modules = [], {}
    for pl in ProfileData.from_serialized_xspace(raw).planes:
        if not DEVICE_PLANE.match(pl.name):
            continue
        runs: List[List[Any]] = []
        events: List[Tuple[str, float, float]] = []
        for ln in pl.lines:
            if ln.name == MODULES_LINE:
                runs = sorted(([e.name, float(e.start_ns),
                                float(e.duration_ns)] for e in ln.events),
                              key=lambda r: r[1])
            elif ln.name == OPS_LINE:
                events = [(instruction_of(e.name), float(e.start_ns),
                           float(e.duration_ns)) for e in ln.events]
        if not events:
            continue
        t0 = min(e[1] for e in events)
        starts = [r[1] for r in runs]
        index: Dict[str, int] = {}
        ops = []
        for name, start, dur in events:
            k = bisect.bisect_right(starts, start) - 1
            held = (k if k >= 0 and start < runs[k][1] + runs[k][2] + EDGE_NS
                    else -1)
            if held >= 0:
                mod = runs[held][0]
                src = hlo.get(mod)
                if src is None:                # another id for one program
                    same = by_stem.get(mod.split("(")[0], [])
                    src = hlo[same[0]] if len(same) == 1 else {}
                modules.setdefault(mod, {})[name] = src.get(name, "")
            ops.append([index.setdefault(name, len(index)),
                        round(start - t0, 3), round(dur, 3), held])
        planes.append({"name": pl.name, "t0_ns": t0, "lo_ns": 0.0,
                       "hi_ns": round(max(e[1] + e[2] for e in events) - t0,
                                      3),
                       "names": list(index),
                       "runs": [[r[0], round(r[1] - t0, 3), round(r[2], 3)]
                                for r in runs],
                       "ops": ops})
    return {"vocabulary": program_vocabulary(), "modules": modules,
            "planes": planes}


def neutral_of(trace_dir: str) -> Optional[Dict[str, Any]]:
    """The capture under ``trace_dir`` in the neutral form: read from
    the file kept beside it, made by a subprocess the first time."""
    cached = os.path.join(trace_dir, NEUTRAL_FILE)
    if not os.path.exists(cached):
        if find_xplane(trace_dir) is None:
            return None
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.setdefault("TPU_LOG_DIR", "disabled")
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--neutral",
             trace_dir], env=env, capture_output=True, text=True, timeout=600)
        if p.returncode != 0 or not os.path.exists(cached):
            sys.stderr.write("scopes: no neutral form of "
                             f"{trace_dir}: {p.stderr[-800:]}\n")
            return None
    with open(cached, "r", encoding="utf-8") as f:
        return json.load(f)


# -- neutral -> numbers (standard library) -------------------------------------


def scope_path(name: str, vocabulary) -> str:
    """``jit(mixed_chunk)/jit(main)/decode_loop/while/body/
    jit(forward_decode)/qkv/dot_general`` -> ``decode_loop/qkv``."""
    # XLA joins the names of instructions it merged with ";": the first
    # is the instruction's own.
    kept = [c for c in name.split(";")[0].split("/") if c in vocabulary]
    return "/".join(kept) if kept else UNSCOPED


def program_of(module: str) -> str:
    """``jit_mixed_chunk(18121249910375047366)`` -> ``jit_mixed_chunk``."""
    return module.split("(")[0]


def self_events(ops: List[List[Any]], path_fn
                ) -> Iterator[Tuple[List[Any], float, str]]:
    """Each event of one line with its self time in ns
    (``tracered.self_times``' rule, event by event) and its path:
    ``path_fn(event)``, or where that is ``UNSCOPED`` the path of the
    event it runs inside (a copy the compiler put into a loop's body
    carries no ``op_name``; the ``while`` around it does)."""
    stack: List[List[Any]] = []          # [end, children ns, event, path]
    for ev in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and ev[1] >= stack[-1][0]:
            _end, child, done, path = stack.pop()
            yield done, max(0.0, done[2] - child), path
        path = path_fn(ev)
        if stack:
            stack[-1][1] += ev[2]
            if path == UNSCOPED:
                path = stack[-1][3]
        stack.append([ev[1] + ev[2], 0.0, ev, path])
    while stack:
        _end, child, done, path = stack.pop()
        yield done, max(0.0, done[2] - child), path


def reduce_neutral(trace: Dict[str, Any],
                   decode_attn: Optional[str] = None
                   ) -> Optional[Dict[str, Any]]:
    """``None`` unless the capture holds a device plane with operations
    inside whole program runs and the program has a vocabulary.
    Otherwise, averaged over the device planes as ``tracered`` does:

        {"devices", "busy_s": self time of every operation of the whole
             runs (= the sum of "paths"), "whole_runs", "cut_runs",
         "paths": {path: [seconds, events]},
         "programs": {program: {"runs", "seconds": the runs' own
             durations, "busy_s", "paths": {path: [seconds, events]},
             "decode_attn_calls": those under ``decode_loop``}},
         "ops": {operation: [seconds, events]} (``tracered``'s names,
             over the same whole runs: what conservation is held to),
         "top": {path: [[operation, seconds], ...three]}}

    ``decode_attn``: the family's ``shapes.DECODE_ATTN`` pattern; the
    calls are counted only where it is given."""
    vocabulary = set(trace.get("vocabulary") or ())
    planes = [p for p in trace.get("planes", []) if p.get("ops")]
    if not vocabulary or not planes:
        return None
    attn_rx = re.compile(decode_attn) if decode_attn else None
    n = len(planes)
    paths: Dict[str, List[float]] = {}
    programs: Dict[str, Dict[str, Any]] = {}
    by_op: Dict[str, List[float]] = {}
    inside: Dict[str, Dict[str, float]] = {}
    whole_n = cut_n = 0
    path_of: Dict[Tuple[str, str], str] = {}
    for p in planes:
        lo, hi = p["lo_ns"], p["hi_ns"]
        whole = [r[1] > lo + EDGE_NS and r[1] + r[2] < hi - EDGE_NS
                 for r in p["runs"]]
        whole_n += sum(whole)
        cut_n += len(whole) - sum(whole)
        for k, r in enumerate(p["runs"]):
            if whole[k]:
                prog = programs.setdefault(program_of(r[0]), {
                    "runs": 0, "seconds": 0.0, "busy_s": 0.0, "paths": {},
                    "decode_attn_calls": 0})
                prog["runs"] += 1 / n
                prog["seconds"] += r[2] / 1e9 / n
        held = [e for e in p["ops"] if e[3] >= 0 and whole[e[3]]]

        def own_path(ev, p=p):
            key = (p["runs"][ev[3]][0], p["names"][ev[0]])
            path = path_of.get(key)
            if path is None:
                path = path_of[key] = scope_path(
                    trace["modules"].get(key[0], {}).get(key[1], ""),
                    vocabulary)
            return path

        for ev, self_ns, path in self_events(held, own_path):
            module = p["runs"][ev[3]][0]
            name = p["names"][ev[0]]
            s = self_ns / 1e9 / n
            op = op_name(name)
            prog = programs[program_of(module)]
            prog["busy_s"] += s
            for table, k2 in ((paths, path), (prog["paths"], path),
                              (by_op, op)):
                acc = table.setdefault(k2, [0.0, 0])
                acc[0] += s
                acc[1] += 1
            ins = inside.setdefault(path, {})
            ins[op] = ins.get(op, 0.0) + s
            if (attn_rx is not None and attn_rx.search(op)
                    and path.split("/")[0] == LOOP):
                prog["decode_attn_calls"] += 1 / n
    if not paths:
        return None
    return {"devices": n, "busy_s": sum(v[0] for v in paths.values()),
            "whole_runs": whole_n / n, "cut_runs": cut_n / n,
            "paths": dict(sorted(paths.items(), key=lambda kv: -kv[1][0])),
            "programs": programs, "ops": by_op,
            "top": {path: [[k, v] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:3]]
                for path, ops in inside.items()}}


def under(red: Dict[str, Any], first: str, modules=None,
          without: Optional[str] = None) -> float:
    """Seconds of the paths whose first component is ``first`` — of
    those that hold one of ``modules``, where given, and not
    ``without``."""
    total = 0.0
    for path, (seconds, _events) in red["paths"].items():
        parts = path.split("/")
        if parts[0] != first or (without and without in parts):
            continue
        if modules is None or any(m in parts for m in modules):
            total += seconds
    return total


def runs_holding(red: Dict[str, Any], first: str) -> float:
    """Whole runs of the programs that hold a path under ``first``."""
    return sum(p["runs"] for p in red["programs"].values()
               if any(k.split("/")[0] == first for k in p["paths"]))


def decode_attn_calls(red: Dict[str, Any]) -> float:
    return sum(p["decode_attn_calls"] for p in red["programs"].values())


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction of the run's FIRST capture by scope, with the
    family's decode attention counted under ``decode_loop``; ``None``
    where there is no capture or the program names nothing. Kept on
    the run, so the readers of one run reduce once."""
    if "_scopes" not in run:
        caps = run.get("captures") or []
        trace_dir = caps[0].get("dir") if caps else None
        trace = (neutral_of(trace_dir)
                 if trace_dir and os.path.isdir(trace_dir) else None)
        pattern = None
        if trace and run.get("family_dir"):
            from benchmark.harness.readers import family_shapes
            pattern = family_shapes(run).DECODE_ATTN
        run["_scopes"] = reduce_neutral(trace, pattern) if trace else None
    return run["_scopes"]


def plain_steps(run: Dict[str, Any]) -> Optional[float]:
    """Decode steps the device ran under ``decode_loop`` in the whole
    runs: the decode attention calls there over the calls the family
    makes a step."""
    red = of_run(run)
    if red is None:
        return None
    from benchmark.harness.readers import family_shapes
    per_step = family_shapes(run).attn_calls_per_step(run["config"]["model"])
    calls = decode_attn_calls(red)
    return calls / per_step if calls else None


def per_plain_step_ms(run: Dict[str, Any], modules=None) -> Optional[float]:
    steps = plain_steps(run)
    if not steps:
        return None
    return under(of_run(run), LOOP, modules) / steps * 1e3


def per_mixed_run_ms(run: Dict[str, Any], modules=None,
                     without: Optional[str] = None) -> Optional[float]:
    red = of_run(run)
    runs = runs_holding(red, MIXED) if red else 0
    if not runs:
        return None
    return under(red, MIXED, modules, without) / runs * 1e3


def share_of_busy(run: Dict[str, Any], seconds) -> Optional[float]:
    red = of_run(run)
    if red is None or red["busy_s"] <= 0:
        return None
    return 100.0 * seconds(red) / red["busy_s"]


# -- the table, the sample ---------------------------------------------------------


def table(red: Dict[str, Any]) -> str:
    """path, seconds, share of busy, ms a run of the programs that hold
    it, top three operations."""
    rows = [f"{'path':44} {'seconds':>9} {'busy %':>7} {'ms/run':>9}  top"]
    for path, (seconds, _events) in red["paths"].items():
        runs = sum(p["runs"] for p in red["programs"].values()
                   if path in p["paths"])
        top = ", ".join(f"{k} {v * 1e3:.1f}" for k, v in red["top"][path])
        rows.append(f"{path:44} {seconds:9.4f} "
                    f"{100 * seconds / red['busy_s']:7.2f} "
                    f"{seconds / runs * 1e3 if runs else 0:9.3f}  {top}")
    rows.append("")
    for name, p in sorted(red["programs"].items(),
                          key=lambda kv: -kv[1]["seconds"]):
        rows.append(f"{name:44} {p['seconds']:9.4f} s in {p['runs']:.0f} "
                    f"whole runs, {p['busy_s']:.4f} s busy, "
                    f"{p['decode_attn_calls']:.0f} decode attention calls "
                    f"under {LOOP}")
    rows.append(f"{red['cut_runs']:.0f} runs cut by the capture's edges "
                "are not counted")
    return "\n".join(rows)


def sample(trace_dir: str, out_path: str, millis: float) -> None:
    """Cut ``millis`` ms out of a capture, from the start of its first
    whole run of a program that holds a mixed step, and write them in
    the neutral form with the instructions that ran there: how
    ``selftest/data`` gets a small recorded trace."""
    tr = neutral_of(trace_dir)
    assert tr is not None, trace_dir
    vocabulary = set(tr["vocabulary"])
    mixed = {m for m, ins in tr["modules"].items()
             if any(scope_path(v, vocabulary).split("/")[0] == MIXED
                    for v in ins.values())}
    for p in tr["planes"]:
        t0 = min(r[1] for r in p["runs"]
                 if r[0] in mixed and r[1] > p["lo_ns"] + EDGE_NS)
        t1 = t0 + millis * 1e6
        keep = {k: i for i, k in enumerate(
            k for k, r in enumerate(p["runs"])
            if r[1] >= t0 and r[1] + r[2] <= t1)}
        p["ops"] = [[e[0], round(e[1] - t0, 3), e[2], keep.get(e[3], -1)]
                    for e in p["ops"] if e[1] >= t0 and e[1] + e[2] <= t1]
        p["runs"] = [[r[0], round(r[1] - t0, 3), r[2]]
                     for k, r in enumerate(p["runs"]) if k in keep]
        used = {k: i for i, k in enumerate(sorted({e[0] for e in p["ops"]}))}
        p["names"] = [p["names"][k] for k in used]
        for e in p["ops"]:
            e[0] = used[e[0]]
        p["t0_ns"] += t0
        p["lo_ns"], p["hi_ns"] = p["lo_ns"] - t0, p["hi_ns"] - t0
    ran = {(p["runs"][e[3]][0], p["names"][e[0]])
           for p in tr["planes"] for e in p["ops"] if e[3] >= 0}
    tr["modules"] = {m: {k: v for k, v in ins.items() if (m, k) in ran}
                     for m, ins in tr["modules"].items()}
    tr["modules"] = {m: ins for m, ins in tr["modules"].items() if ins}
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(tr, f, separators=(",", ":"))


def main(argv: List[str]) -> int:
    if argv[:1] == ["--neutral"]:
        trace_dir = argv[1]
        tmp = os.path.join(trace_dir, f"{NEUTRAL_FILE}.{os.getpid()}.tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(xplane_to_neutral(find_xplane(trace_dir) or ""), f,
                      separators=(",", ":"))
        os.replace(tmp, os.path.join(trace_dir, NEUTRAL_FILE))
        return 0
    if len(argv) == 3:
        sample(argv[0], argv[1], float(argv[2]))
        return 0
    if len(argv) not in (1, 2):
        sys.stderr.write(
            "usage: scopes.py <trace_dir> [<decode attention pattern>]\n"
            "       scopes.py <trace_dir> <out.json> <millis>\n")
        return 2
    trace = neutral_of(argv[0])
    red = reduce_neutral(trace, argv[1] if len(argv) > 1 else None) \
        if trace else None
    if red is None:
        sys.stderr.write(f"scopes: nothing to read under {argv[0]} (no "
                         "capture, no device plane, or a program that "
                         "names nothing)\n")
        return 1
    print(table(red))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
