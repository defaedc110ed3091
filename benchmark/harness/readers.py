"""What the metric files under ``metrics/`` are built from. A reader
takes the run (``run.py`` ``drive``: the window's request records, the
marks at its two edges, the reduced captures, the configuration) and
returns one number, or ``None`` where there is nothing to read."""

from __future__ import annotations

import re
from types import ModuleType
from typing import Any, Callable, Dict, Optional, Tuple

from benchmark.harness import contract, stats
from benchmark.harness.peaks import peaks_for

Run = Dict[str, Any]
Reader = Callable[[Run], Optional[float]]


def tail_of(sample: Callable[[Dict], Optional[float]], q: float,
            failed_sort_last: bool = True) -> Reader:
    """The ``q``-th percentile of ``sample`` over the window's requests.
    A request that failed is missing and sorts last."""
    def read(run: Run) -> Optional[float]:
        good = [r for r in run["requests"] if r.get("ok")]
        values = stats.collect(good, sample)
        missing = (len(run["requests"]) - len(good)) if failed_sort_last else 0
        return stats.percentile(values, q, missing)
    return read


def stage_tail(a: Tuple[str, ...], b: Tuple[str, ...], q: float) -> Reader:
    """Percentile of the time between two recorder stages (the first of
    ``a`` and the first of ``b`` that the request has)."""
    def sample(r: Dict) -> Optional[float]:
        st = r.get("stages") or {}
        ta = next((st[k] for k in a if k in st), None)
        tb = next((st[k] for k in b if k in st), None)
        return None if ta is None or tb is None else (tb - ta) * 1e3
    return tail_of(sample, q, failed_sort_last=False)


def context_split(r: Dict) -> Optional[Tuple[int, int]]:
    """(cached, new) prompt tokens of a finished request. The engine
    reports a continued conversation's ``prompt_tokens`` without its
    cached history and a radix hit's with it; the plan knows which."""
    meta = r.get("meta") or {}
    if meta.get("prompt_tokens") is None:
        return None
    cached = int(meta.get("cached_tokens") or 0)
    prompt = int(meta["prompt_tokens"])
    if r.get("turn", 0) > 0 and prompt == r.get("planned_prompt_tokens"):
        return cached, prompt
    return cached, max(0, prompt - cached)


def capture(run: Run, index: int = 0) -> Optional[Dict[str, Any]]:
    caps = [c for c in run.get("captures", [])
            if (c.get("reduced") or {}).get("devices")]
    return caps[index] if len(caps) > index else None


def family_shapes(run: Run) -> ModuleType:
    """``shapes.py`` of the run's model family: its shape functions and
    the names of its kernels in a trace."""
    return contract.load_family(run["family_dir"], "shapes")


def ops_time(cap: Dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v[0] for k, v in cap["reduced"]["ops"].items()
               if rx.search(k))


def decode_steps(run: Run, cap: Dict) -> Optional[float]:
    """Decode steps the device ran inside the capture: calls of the
    decode attention kernel over the calls the family makes a step."""
    progs = cap["reduced"].get("programs", {})
    calls = sum(v[2] for v in progs.values())
    per_step = family_shapes(run).attn_calls_per_step(run["config"]["model"])
    return calls / per_step if calls else None


def decode_module_time(cap: Dict) -> float:
    """Device time of the program runs that decode: decode chunks and
    mixed chunks (their prefill slices included)."""
    progs = cap["reduced"].get("programs", {})
    return sum(progs.get(k, [0.0])[0] for k in ("decode", "mixed"))


def mean_load(cap: Dict) -> Optional[Tuple[float, float]]:
    """Mean rows decoding and mean context tokens they attend to, over
    the samples taken while the capture was held."""
    s = [x for x in cap.get("samples", []) if x["rows"] > 0]
    if not s:
        return None
    return (sum(x["rows"] for x in s) / len(s),
            sum(x["context_tokens"] for x in s) / len(s))


def itemsizes(run: Run) -> Tuple[int, int]:
    m = run["config"]["server"]["model"]
    return (1 if m.get("quantization") == "int8" else 2,
            1 if m.get("kv_quantization") == "int8" else 2)


def decode_step_ms(run: Run) -> Optional[float]:
    cap = capture(run)
    if cap is None or not decode_steps(run, cap):
        return None
    return decode_module_time(cap) / decode_steps(run, cap) * 1e3


def least_time(run: Run, nbytes: float, flops: float, int8: bool) -> float:
    pk = peaks_for(run["device"]["kind"])
    return max(nbytes / pk["hbm_bytes_per_s"],
               flops / (pk["int8_ops"] if int8 else pk["bf16_flops"]))


def prefill_work(run: Run, cap: Dict) -> Tuple[float, float, float]:
    """(new tokens, new x visible context, context tokens) of the
    prefill work that fell inside the capture, each request counted by
    the share of its prefill span that the capture covers."""
    new_t, pairs, ctx = 0.0, 0.0, 0.0
    a, b = cap["t_begin"], cap["t_end"]
    for r in run["requests"]:
        st = r.get("stages") or {}
        t0 = st.get("prefill_start", st.get("admitted"))
        t1 = st.get("prefill_done", st.get("first_token"))
        split = context_split(r)
        if t0 is None or t1 is None or t1 <= t0 or split is None:
            continue
        share = max(0.0, min(b, t1) - max(a, t0)) / (t1 - t0)
        cached, new = split
        new_t += share * new
        pairs += share * (new * cached + new * (new + 1) / 2.0)
        ctx += share * (cached + new)
    return new_t, pairs, ctx
