"""Open-loop traffic from a mix file (``bench/traffic/<mix>.json``).

A mix file holds only data:

    {"arrivals": "poisson", "rate_rps": 5.6,          # or
     "arrivals": "backlog", "backlog": 32,
     "prompt": {"dist": "lognormal", "lo": 128, "hi": 768},
     "output": {"dist": "lognormal", "lo": 64, "hi": 256},
     "base_seed": 0}

Every seed gets the SAME multiset of request sizes and, for Poisson
mixes, the same multiset of inter-arrival gaps inside the window, drawn
once from ``base_seed``; ``--seed`` only permutes their order and draws
the token ids. So two seeds do the same amount of work, in another
order, and their spread is the system's and not the generator's. The
window's gaps are a Poisson process conditioned on its count
(``round(rate * seconds)`` arrivals, gaps rescaled to fill the window
exactly); arrivals after the window, which keep the load up while the
window's requests drain, continue at the same rate.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np


def sample_len(rng, lo: int, hi: int, dist: str) -> int:
    """Length sampler, copied from the program's trace generator so that
    a change there cannot move this yardstick."""
    if dist == "fixed":
        return hi
    if dist == "bimodal":
        return lo if rng.random() < 0.5 else hi
    if dist == "uniform":
        return int(rng.integers(lo, hi + 1))
    if dist == "lognormal":
        return int(np.clip(round(lo * rng.lognormal(0.0, 0.8)), lo, hi))
    if dist == "zipf":
        return int(np.clip(lo - 1 + rng.zipf(2.0), lo, hi))
    raise ValueError(f"unknown dist {dist!r}")


@dataclass(frozen=True)
class Planned:
    """One request of the plan. ``due_s`` is seconds from the window's
    start (backlog mixes: None, due when submitted)."""
    rid: int
    prompt: tuple
    gen_len: int
    due_s: float | None
    in_window: bool


def load_mix(path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("arrivals") not in ("poisson", "backlog"):
        raise ValueError(f"{path}: arrivals must be poisson or backlog")
    return mix


def _sizes(mix: dict, n: int, base_seed: int):
    rng = np.random.default_rng(base_seed)
    p, o = mix["prompt"], mix["output"]
    return [(sample_len(rng, p["lo"], p["hi"], p["dist"]),
             sample_len(rng, o["lo"], o["hi"], o["dist"])) for _ in range(n)]


def plan(mix: dict, seed: int, seconds: float, vocab: int,
         extra_s: float = 0.0) -> List[Planned]:
    """The requests of one run, in due order.

    Poisson: ``round(rate * seconds)`` requests due inside the window,
    then ``ceil(rate * extra_s)`` more after it. Backlog: a pool of
    requests large enough for ``seconds`` at any plausible rate; the
    harness submits them in order as the backlog drains, so ``due_s``
    is None and all count as in the window when submitted inside it."""
    base = int(mix.get("base_seed", 0))
    rng = np.random.default_rng(int(seed))
    if mix["arrivals"] == "poisson":
        rate = float(mix["rate_rps"])
        n_in = max(1, int(round(rate * seconds)))
        n_out = int(math.ceil(rate * extra_s))
        brng = np.random.default_rng(base + 1)
        gaps_in = brng.exponential(1.0 / rate, size=n_in + 1)
        gaps_in = gaps_in / gaps_in.sum() * seconds
        gaps_in = rng.permutation(gaps_in)
        due_in = np.cumsum(gaps_in)[:n_in]
        due_out = seconds + np.cumsum(
            brng.exponential(1.0 / rate, size=n_out))
        dues = [float(d) for d in np.concatenate([due_in, due_out])]
        sizes = _sizes(mix, n_in + n_out, base)
        order_in = rng.permutation(n_in)
        sizes = [sizes[i] for i in order_in] + sizes[n_in:]
    else:
        n_in = int(mix.get("pool", 4096))
        dues = [None] * n_in
        sizes = _sizes(mix, n_in, base)
        sizes = [sizes[i] for i in rng.permutation(n_in)]
    out = []
    for rid, ((plen, glen), due) in enumerate(zip(sizes, dues)):
        prompt = tuple(int(t) for t in rng.integers(1, vocab, size=plen))
        out.append(Planned(rid=rid, prompt=prompt, gen_len=int(glen),
                           due_s=due, in_window=rid < n_in))
    return out
