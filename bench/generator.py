"""The one traffic generator. Every mix is a data file of parameters
(bench/traffic/<mix>.json) that this module reads; a new mix is a new
file, not new code.

Sizes and arrivals come from the mix's fixed `template_seed`, so every
run seed serves the same set of prompt lengths, output lengths, system
prompt choices and inter-arrival gaps. The run seed only reorders them
within blocks of `order_block` requests and draws the token ids. That
keeps the work of a run the same from seed to seed while no two seeds
send the same tokens.

Arrival kinds:
  backlog  — closed: every request is queued when the run starts; the
             system always has more waiting than it can drain.
  poisson  — open: exponential gaps at `rate_per_s`; a request is due at
             its time whether or not earlier ones have finished.

A mix may mark its system prompts `prewarm`: they are served once,
before arrivals start, so the prefix cache holds them as it would in a
server that has been up for a while.

A backlog starts in its steady state, so that requests finish and are
admitted all through the window: the first `slots` requests are drawn
from the steady state of a closed system in which each slot serves one
request after another, and the backlog follows them. Each such resident
request is caught part-way: its output length is drawn in proportion
to the length itself (a slot spends longer in a long request), it has
already produced `age` of it, uniform below that length, and it has the
rest to go. Its context then holds `age` tokens besides its prompt. The
`age` tokens are the first `age` of one shared history of the run seed,
served once before arrivals (so the prefix cache holds it) and placed
ahead of the prompt, so filling a slot with them costs a prefix hit and
not a prefill; the attention over them, and the memory they take, cost
what the request's own tokens would.

Prompt ids: `uniform` over the vocabulary, or `zipf_topics` (copied
from the idea of repro.core.traces.synth_request_trace): Zipf(a) ranks
over a per-topic permutation of the vocabulary, so a few ids, and the
experts they route to, dominate; the topic mix shifts every `phase_s`
seconds, which moves the hot set.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import List

import numpy as np


@dataclasses.dataclass
class Schedule:
    due_s: np.ndarray  # [N] seconds after arrivals start (0 for a backlog)
    prompts: List[np.ndarray]  # int32 token ids
    max_new: np.ndarray  # [N]
    closed: bool  # True: all requests queued at start
    # prefixes a long-running server already holds in its prefix cache:
    # served once, one token each, before arrivals start
    warm_prompts: List[np.ndarray] = dataclasses.field(default_factory=list)
    # requests [0, resident) start part-way through, in the steady state
    resident: int = 0


def load_mix(bench_dir: str, name: str) -> dict:
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    kind = spec["dist"]
    if kind == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n)
    elif kind == "lognormal":
        x = np.rint(spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n)))
    elif kind == "fixed":
        x = np.full(n, spec["value"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(x, spec.get("min", 1), spec.get("max", np.inf)).astype(np.int64)


def length_bounds(spec: dict):
    if spec["dist"] == "fixed":
        return spec["value"], spec["value"]
    return spec["min"], spec["max"]


def max_prompt_len(mix: dict) -> int:
    p = mix["prompt"]
    return p.get("system", {}).get("length", 0) + length_bounds(p["length"])[1]


def min_prompt_len(mix: dict) -> int:
    p = mix["prompt"]
    return p.get("system", {}).get("length", 0) + length_bounds(p["length"])[0]


def cache_len(mix: dict) -> int:
    """Per-slot context the mix can need: the longest prompt plus the
    longest output."""
    return max_prompt_len(mix) + length_bounds(mix["max_new_tokens"])[1]


def _zipf_p(n: int, a: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** (-a)
    return p / p.sum()


def _block_perm(rng, n: int, block: int) -> np.ndarray:
    order = np.arange(n)
    for s in range(0, n, block):
        order[s:s + block] = s + rng.permutation(min(block, n - s))
    return order


def n_requests(mix: dict, horizon_s: float) -> int:
    arr = mix["arrival"]
    if arr["kind"] == "backlog":
        return int(arr["requests"])
    # enough for the horizon with a wide margin; the rest are never due
    return int(arr["rate_per_s"] * horizon_s * 1.5) + 64


def _residents(tmpl, mix: dict, slots: int):
    """(prompt lengths, ages, outputs still to go) of `slots` requests in
    the steady state of a closed backlog (see the module's docstring)."""
    pool = _lengths(tmpl, mix["max_new_tokens"], 64 * slots)
    total = tmpl.choice(pool, size=slots, p=pool / pool.sum())
    age = np.floor(tmpl.random(slots) * total).astype(np.int64)
    plen = _lengths(tmpl, mix["prompt"]["length"], slots)
    return plen, age, total - age


def generate(mix: dict, vocab: int, seed: int, horizon_s: float,
             slots: int = 0) -> Schedule:
    """The schedule of one run. `horizon_s` is how long arrivals must
    last (open mixes); `slots` is the number of decode slots, which a
    backlog fills with resident requests."""
    n = n_requests(mix, horizon_s)
    tmpl = np.random.default_rng(mix["template_seed"])
    rng = np.random.default_rng(seed)
    p = mix["prompt"]
    arr = mix["arrival"]

    # --- the template: the same for every seed
    plen = _lengths(tmpl, p["length"], n)
    max_new = _lengths(tmpl, mix["max_new_tokens"], n)
    if arr["kind"] == "poisson":
        gaps = tmpl.exponential(1.0 / arr["rate_per_s"], n)
    elif arr["kind"] == "backlog":
        gaps = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    sysp = p.get("system")
    sys_choice = (
        tmpl.choice(sysp["count"], size=n, p=_zipf_p(sysp["count"], sysp["zipf"]))
        if sysp else np.zeros(n, np.int64)
    )
    topic_u = tmpl.random(n)

    # --- the run seed reorders within blocks and draws the tokens
    order = _block_perm(rng, n, int(mix["order_block"]))
    plen, max_new, gaps = plen[order], max_new[order], gaps[order]
    sys_choice, topic_u = sys_choice[order], topic_u[order]
    due = np.cumsum(gaps) - gaps[0] if arr["kind"] == "poisson" else gaps

    ids = p["ids"]
    if ids["dist"] == "uniform":
        def draw(k, _t):
            return rng.integers(0, vocab, k)
    elif ids["dist"] == "zipf_topics":
        perms = [rng.permutation(vocab) for _ in range(ids["topics"])]
        zp = _zipf_p(vocab, ids["zipf"])

        def draw(k, t):
            return perms[t][rng.choice(vocab, size=k, p=zp)]
    else:
        raise ValueError(f"unknown id distribution {ids['dist']!r}")

    systems = (
        [rng.integers(0, vocab, sysp["length"]) for _ in range(sysp["count"])]
        if sysp else []
    )
    prompts = []
    n_topics = ids.get("topics", 1)
    for i in range(n):
        topic = 0
        if n_topics > 1:
            # the phase's dominant topic takes `dominant` of the requests
            lead = int(due[i] // ids["phase_s"]) % n_topics
            u = topic_u[i]
            if u < ids["dominant"]:
                topic = lead
            else:
                k = int((u - ids["dominant"]) / (1 - ids["dominant"]) * (n_topics - 1))
                topic = (lead + 1 + min(k, n_topics - 2)) % n_topics
        user = draw(int(plen[i]), topic)
        head = systems[sys_choice[i]] if sysp else np.zeros(0, np.int64)
        prompts.append(np.concatenate([head, user]).astype(np.int32))
    warm = [x.astype(np.int32) for x in systems] if sysp and sysp.get("prewarm") else []
    resident = slots if arr["kind"] == "backlog" else 0
    if resident:
        r_plen, r_age, r_left = _residents(tmpl, mix, resident)
        history = rng.integers(0, vocab, int(r_age.max())).astype(np.int32)
        lead = [np.concatenate([history[:a], draw(int(n), 0)]).astype(np.int32)
                for a, n in zip(r_age, r_plen)]
        prompts = lead + prompts
        max_new = np.concatenate([r_left, max_new])
        due = np.concatenate([np.zeros(resident), due])
        warm = warm + [history]
    return Schedule(due_s=due.astype(np.float64), prompts=prompts,
                    max_new=max_new.astype(np.int64),
                    closed=arr["kind"] == "backlog", warm_prompts=warm,
                    resident=resident)
