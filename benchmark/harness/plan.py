"""Expand a traffic file into a plan: every request of a run, with the
instant it is due, its lengths, its tier and its text.

One general generator reads every mix. A mix is data (``traffic/*.json``):

``loop``            ``"open"`` (requests are due at planned instants,
                    whatever the server does) or ``"closed"`` (each
                    client sends its next request when the last ended).
``period_s``,       open loop: time is cut into equal periods, each a
``segments``        list of stretches ``{"name", "seconds", "arrivals"}``;
                    ``arrivals`` sessions start in each, evenly spaced.
``prompt_tokens``,  length classes ``{"range": [lo, hi], "share"}``; how
``output_tokens``   many requests of each class fall in each stretch is
                    fixed by the shares (largest remainder), and the
                    lengths inside a class are an even grid over its
                    range, so every seed offers the same multiset.
``tiers``           ``{"name", "priority", "share", "timeout_s"}``.
``session``         optional: ``system_prompts`` x ``system_tokens``
                    shared prefixes, ``turns`` classes
                    ``{"count", "share"}`` and ``gap_s`` ``[lo, hi]``
                    between the due instants of consecutive turns.
``ramp_periods``    whole periods of the same traffic run before the
                    window opens (set-up, not counted).
``tail_s``          the plan goes on this long after the window closes,
                    so its last requests see the same contention.
``drain_s``         how long the window's requests may take to finish
                    after it closed; what is left counts as failed.
``clients_per_row``,closed loop: clients per batch row of the
``requests_per_client``  configuration, and how many requests each holds.

**The skeleton** (due instants, sessions, turn counts, gaps, which
shared prefix, and every request's lengths and tier) depends on the
file alone: lengths are an even grid over each class's range, placed by
a shuffle of the skeleton's own, new for each period and stretch.
``--seed`` decides the text of every prompt (and, in the child, the
weights). In a closed loop, where nothing is due at an instant, the
seed also permutes the lengths among the clients. Standard library
only.
"""

from __future__ import annotations

import math
import random
from typing import Any, Dict, List, Sequence, Tuple

ALPHABET = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"
#: Seeds the skeleton's own shuffles: a constant, so that the skeleton
#: is the same for every ``--seed`` and every period.
SKELETON_SEED = 20260927


def largest_remainder(shares: Sequence[float], n: int) -> List[int]:
    """``n`` items split by ``shares``; the counts sum to ``n``."""
    total = float(sum(shares))
    exact = [s / total * n for s in shares]
    counts = [int(math.floor(x)) for x in exact]
    order = sorted(range(len(shares)),
                   key=lambda i: (exact[i] - counts[i], -i), reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def class_grid(classes: Sequence[Dict], n: int) -> List[int]:
    """``n`` lengths: each class gets its share of them, spread evenly
    over its range. The same list for every seed."""
    out: List[int] = []
    counts = largest_remainder([c["share"] for c in classes], n)
    for c, k in zip(classes, counts):
        lo, hi = c["range"]
        out.extend(int(round(lo + (hi - lo) * (i + 0.5) / k))
                   for i in range(k))
    return out


def tier_grid(tiers: Sequence[Dict], n: int) -> List[Dict]:
    out: List[Dict] = []
    for t, k in zip(tiers, largest_remainder([t["share"] for t in tiers], n)):
        out.extend([t] * k)
    return out


def text(rng: random.Random, n: int) -> str:
    """``n`` bytes of letters and digits: ``n`` tokens of the byte
    tokenizer, no word the preprocessor's priority lexicon knows."""
    return "".join(rng.choices(ALPHABET, k=n))


def _period_skeleton(traffic: Dict) -> List[Dict]:
    """The sessions that start in one period: offset of each turn from
    the period's start, turn by turn. The same for every period."""
    rng = random.Random(SKELETON_SEED)
    starts: List[Tuple[float, str]] = []
    t0 = 0.0
    for seg in traffic["segments"]:
        n = int(seg["arrivals"])
        starts.extend((t0 + seg["seconds"] * (i + 0.5) / n, seg["name"])
                      for i in range(n))
        t0 += seg["seconds"]
    sess = traffic.get("session")
    if not sess:
        return [{"offsets": [t], "prefix": None} for t, _ in starts]
    counts = largest_remainder([c["share"] for c in sess["turns"]],
                               len(starts))
    turns = [c["count"] for c, k in zip(sess["turns"], counts)
             for _ in range(k)]
    rng.shuffle(turns)
    n_gaps = sum(t - 1 for t in turns)
    lo, hi = sess["gap_s"]
    gaps = [lo + (hi - lo) * (i + 0.5) / n_gaps for i in range(n_gaps)]
    rng.shuffle(gaps)
    out = []
    for j, ((t, _), k) in enumerate(zip(starts, turns)):
        offs = [t]
        for _ in range(k - 1):
            offs.append(offs[-1] + gaps.pop())
        out.append({"offsets": offs,
                    "prefix": j % int(sess["system_prompts"])})
    return out


def period_of(traffic: Dict) -> float:
    return float(sum(s["seconds"] for s in traffic["segments"]))


def _segment_at(traffic: Dict, t_in_period: float) -> int:
    acc = 0.0
    for i, seg in enumerate(traffic["segments"]):
        acc += seg["seconds"]
        if t_in_period < acc - 1e-9:
            return i
    return len(traffic["segments"]) - 1


def open_plan(traffic: Dict, seed: int, window_s: float) -> List[Dict]:
    """Every request due from the ramp's start to the tail's end, in
    due order. ``due`` is relative to the window's opening."""
    period = period_of(traffic)
    ramp = int(traffic.get("ramp_periods", 1))
    t_end = window_s + float(traffic.get("tail_s", 0.0))
    skeleton = _period_skeleton(traffic)
    sess = traffic.get("session")
    reqs: List[Dict] = []
    p = -ramp
    while p * period < t_end:
        for j, s in enumerate(skeleton):
            for k, off in enumerate(s["offsets"]):
                due = p * period + off
                if due < t_end:
                    reqs.append({"due": due, "session": (p, j), "turn": k,
                                 "prefix": s["prefix"],
                                 "n_turns": len(s["offsets"])})
        p += 1
    # Lengths and tiers: a seeded permutation of the stretch's grid,
    # over the requests DUE in that stretch of that period.
    groups: Dict[Tuple[int, int], List[Dict]] = {}
    for r in reqs:
        per = int(math.floor(r["due"] / period + 1e-9))
        seg = _segment_at(traffic, r["due"] - per * period)
        groups.setdefault((per, seg), []).append(r)
    for (per, seg), members in sorted(groups.items()):
        members.sort(key=lambda r: (r["due"], r["session"], r["turn"]))
        n = len(members)
        # Which request of the stretch is of which class and tier: the
        # skeleton's own shuffle, one per period and stretch, the same
        # for every seed (a long prompt at a burst's head delays all
        # behind it, so a seed that moved it would change the work).
        srng = random.Random(f"{SKELETON_SEED}/{per}/{seg}")
        tiers = tier_grid(traffic["tiers"], n)
        srng.shuffle(tiers)
        for r, tier in zip(members, tiers):
            r.update(tier=tier["name"], priority=tier["priority"],
                     timeout_s=tier["timeout_s"])
        # The exact length inside a class's range: an even grid over
        # the range, placed by the skeleton too. (A seeded permutation
        # inside the class was tried: six seeds then spread the chat
        # cell's TTFT tail by 5 %, one seed twice by 1.5 %.)
        for key, field in (("prompt_tokens", "user_tokens"),
                           ("output_tokens", "output_tokens")):
            classes = traffic[key]
            counts = largest_remainder([c["share"] for c in classes], n)
            order = [i for i, k in enumerate(counts) for _ in range(k)]
            srng.shuffle(order)
            grids = []
            for c, k in zip(classes, counts):
                g = class_grid([dict(c, share=1.0)], k)
                srng.shuffle(g)
                grids.append(g)
            for r, ci in zip(members, order):
                r[field] = grids[ci].pop()
    # Text, and what a turn carries of its session.
    prefixes: List[str] = []
    if sess:
        prng = random.Random(f"{seed}/prefixes")
        prefixes = [text(prng, int(sess["system_tokens"]))
                    for _ in range(int(sess["system_prompts"]))]
    history: Dict[Tuple[int, int], str] = {}
    reqs.sort(key=lambda r: (r["due"], r["session"], r["turn"]))
    for i, r in enumerate(reqs):
        rng = random.Random(f"{seed}/text/{r['session']}/{r['turn']}")
        body = text(rng, r["user_tokens"])
        if sess:
            sid = r["session"]
            if r["turn"] == 0:
                body = prefixes[r["prefix"]] + body
            r["conversation_id"] = f"s{sid[0] + 1000}-{sid[1]}"
            r["history_text"] = history.get(sid, "")
            history[sid] = r["history_text"] + body
        r["content"] = body
        r["prompt_tokens"] = len(body)
        r["id"] = f"q{i:06d}"
        r["phase"] = ("ramp" if r["due"] < 0 else
                      "window" if r["due"] < window_s else "tail")
    return reqs


def closed_plan(traffic: Dict, seed: int, rows: int,
                max_context: int) -> List[List[Dict]]:
    """One list of requests per client. A client's first request is cut
    as if the client were part-way through it when the run began: the
    output still to come is shortened by the client's phase, so that
    completions come evenly from the start and never in waves. (The
    prompt is not lengthened to stand for the tokens "already made":
    that doubled the prefill backlog of the first instant, and rows sat
    waiting for prefill slices well into the window.)"""
    n_clients = int(math.ceil(float(traffic["clients_per_row"]) * rows))
    per_client = int(traffic["requests_per_client"])
    n = n_clients * per_client
    rng = random.Random(f"{seed}/closed")
    prompts = class_grid(traffic["prompt_tokens"], n)
    outputs = class_grid(traffic["output_tokens"], n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    tier = traffic["tiers"][0]
    phases = [(i + 0.5) / n_clients for i in range(n_clients)]
    random.Random(SKELETON_SEED).shuffle(phases)
    clients: List[List[Dict]] = []
    for c in range(n_clients):
        seq = []
        for k in range(per_client):
            pt, ot = prompts[c * per_client + k], outputs[c * per_client + k]
            if k == 0:
                ot = max(16, ot - int(ot * phases[c]))
            trng = random.Random(f"{seed}/text/{c}/{k}")
            seq.append({"id": f"c{c:03d}-{k:02d}", "client": c, "seq": k,
                        "content": text(trng, pt), "prompt_tokens": pt,
                        "output_tokens": ot, "tier": tier["name"],
                        "priority": tier["priority"],
                        "timeout_s": tier["timeout_s"]})
        clients.append(seq)
    return clients


def offered(reqs: Sequence[Dict]) -> Dict[str, int]:
    """What a window offers: requests, prompt tokens, output tokens."""
    win = [r for r in reqs if r.get("phase") == "window"]
    return {"requests": len(win),
            "prompt_tokens": sum(r["prompt_tokens"] for r in win),
            "output_tokens": sum(r["output_tokens"] for r in win)}


def body_of(r: Dict[str, Any], user_id: str = "bench") -> Dict[str, Any]:
    """The JSON a client posts to ``/api/v1/messages`` for request ``r``."""
    meta: Dict[str, Any] = {"max_new_tokens": r["output_tokens"],
                            "user_priority": r["priority"]}
    if r.get("history_text"):
        meta["history_text"] = r["history_text"]
    body: Dict[str, Any] = {
        "id": r["id"], "content": r["content"], "user_id": user_id,
        "priority": r["priority"], "timeout": r["timeout_s"],
        "metadata": meta}
    if r.get("conversation_id"):
        body["conversation_id"] = r["conversation_id"]
    return body
