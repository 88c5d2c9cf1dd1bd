"""The one general traffic generator: reads a mix file under ``traffic/``.

A mix file fixes the arrival process and the length distributions; the
seed fixes the order and the token ids.  Every seed gets the same set of
prompt lengths, output lengths and inter-arrival gaps (deterministic
quantiles of the stated distributions), shuffled by the seed, so two
seeds do the same amount of work in another order.

Keys of a mix file:

    arrivals      "poisson" (open loop at ``rate_per_s``) or "backlog"
                  (``requests`` requests all due at time 0, queued before
                  the window opens)
    prompt        {"median", "sigma", "min", "max"}: lognormal, clipped;
                  with ``shared_prefix`` this is the user suffix
    output        the same, for the number of new tokens
    shared_prefix {"count", "length", "zipf_s"}: ``count`` system prompts
                  of ``length`` tokens, chosen with Zipf popularity
    lead_s        (open loop, optional) seconds of the same traffic offered
                  before the window opens, so that the window starts in
                  steady state; about one request's lifetime.  Requests due
                  in the lead-in are served but not judged.

All requests are greedy.  Token ids are drawn from the whole vocabulary.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass
class Arrival:
    due: float              # seconds after the window opens (< 0: lead-in)
    prompt: List[int]
    max_new: int
    system: int = -1        # index of the shared system prompt, -1 = none


def load_mix(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def _lognormal_quantiles(spec: dict, n: int, scale: float) -> np.ndarray:
    """``n`` deterministic quantiles of the clipped lognormal, scaled."""
    med = spec["median"] * scale
    lo = max(1, int(round(spec["min"] * scale)))
    hi = max(lo, int(round(spec["max"] * scale)))
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.exp(math.log(med) + spec["sigma"] * z)
    return np.clip(np.round(vals), lo, hi).astype(np.int64)


def _zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """Largest-remainder split of ``n`` requests over ``count`` system
    prompts with Zipf(s) popularity."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - out))[:n - out.sum()]:
        out[i] += 1
    return out


def n_requests(mix: dict, seconds: float) -> int:
    if mix["arrivals"] == "backlog":
        return int(mix["requests"])
    return max(1, int(math.ceil(mix["rate_per_s"] * seconds)))


def generate(mix: dict, *, vocab: int, seed: int, seconds: float,
             scale: float = 1.0, max_len: Optional[int] = None
             ) -> List[Arrival]:
    """The cell's requests, ordered by due time; those of the lead-in
    (``lead_s``) are due before 0, the window's opening.

    ``scale`` shrinks every length (the CPU rehearsal runs the same mix
    at a reduced ``max_len``); ``max_len`` caps prompt plus output so no
    request outgrows the engine."""
    rng = np.random.default_rng(seed)
    out, systems = _draw(mix, n_requests(mix, seconds), seconds, rng,
                         vocab, scale, max_len, None)
    lead = float(mix.get("lead_s", 0.0))
    if lead > 0 and mix["arrivals"] != "backlog":
        early, _ = _draw(mix, n_requests(mix, lead), lead, rng, vocab,
                         scale, max_len, systems)
        for a in early:
            a.due -= lead
        out = early + out
    return out


def _draw(mix, n, seconds, rng, vocab, scale, max_len, systems):
    """``n`` requests due over ``[0, seconds)``, and the system prompts
    (drawn here unless given)."""
    plen = rng.permutation(_lognormal_quantiles(mix["prompt"], n, scale))
    olen = rng.permutation(_lognormal_quantiles(mix["output"], n, scale))
    if mix["arrivals"] == "poisson":
        rate = mix["rate_per_s"]
        gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                         for i in range(n)])
        # the same total for every seed, every request due in the window
        gaps *= (seconds - 0.5 / rate) / gaps.sum()
        gaps = rng.permutation(gaps)
        due = np.cumsum(gaps) - gaps[0]
    elif mix["arrivals"] == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    shared = mix.get("shared_prefix")
    which = np.full(n, -1)
    if shared:
        if systems is None:
            slen = max(1, int(round(shared["length"] * scale)))
            systems = [rng.integers(0, vocab, size=slen).tolist()
                       for _ in range(shared["count"])]
        counts = _zipf_counts(shared["count"], shared["zipf_s"], n)
        which = rng.permutation(np.repeat(np.arange(shared["count"]),
                                          counts))
    out = []
    for i in range(n):
        head = systems[which[i]] if which[i] >= 0 else []
        p = int(plen[i])
        o = int(olen[i])
        if max_len is not None:
            p = min(p, max_len - len(head) - 2)
            o = min(o, max_len - len(head) - p - 1)
        prompt = head + rng.integers(0, vocab, size=p).tolist()
        out.append(Arrival(due=float(due[i]), prompt=prompt, max_new=o,
                           system=int(which[i])))
    out.sort(key=lambda a: a.due)
    return out, systems
