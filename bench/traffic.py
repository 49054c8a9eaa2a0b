"""One generator for every serving traffic mix.

A mix is a JSON file under ``bench/traffic/``; this module reads its
parameters and draws the requests.  The loop is open: requests are due on
a schedule that does not wait for the server.

Arrivals are a Poisson process whose rate is ``factor`` times the base
rate for ``length_s`` of every ``period_s`` (a burst), at a mean of
``rate_per_s``.  Prompt and answer lengths are drawn independently from
clipped log-normals and rounded up to ``round_to``; tenants by their
shares.  One realization of each period is drawn from the mix's own
``draw_seed``, and a run's seed sets the order in which those periods
come, the prompts' tokens and the weights.  So every seed offers the same
work in another order, and the spread between runs with different seeds
is the system's, not the draw's: a p95 over a few hundred requests lies in
the queues of the heaviest bursts, and a fresh draw per seed would move it
by a fifth.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass, replace
from pathlib import Path
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass(frozen=True)
class Arrival:
    due_s: float  # offset from the window's start
    prompt_len: int
    max_new_tokens: int
    tenant: str


def load_mix(name: str) -> Dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of the seed (any non-negative int)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def weights_seed(seed: int) -> int:
    """The seed of a run's weights, below 2**31 for ``jax.random.key``."""
    return int(rng_for(seed, 3).integers(0, 2**31))


def lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` log-normal lengths, clipped and rounded up."""
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    x = np.clip(np.ceil(x), spec["min"], spec["max"])
    step = spec.get("round_to", 1)
    return (np.ceil(x / step) * step).astype(np.int64)


def phases(mix: Dict, seconds: float):
    """(start, length, rate) of every phase in ``[0, seconds)``."""
    rate = float(mix["rate_per_s"])
    b = mix.get("burst")
    if not b:
        return [(0.0, seconds, rate)]
    P, L, f, off = b["period_s"], b["length_s"], b["factor"], b["offset_s"]
    base = rate * P / (P - L + f * L)
    out = []
    for k in range(int(math.ceil(seconds / P))):
        t0 = k * P
        for s, e, r in ((t0, t0 + off, base), (t0 + off, t0 + off + L, base * f),
                        (t0 + off + L, t0 + P, base)):
            s, e = min(s, seconds), min(e, seconds)
            if e > s:
                out.append((s, e - s, r))
    return out


def period_s(mix: Dict, seconds: float) -> float:
    b = mix.get("burst")
    return float(b["period_s"]) if b else float(seconds)


def draw_period(mix: Dict, k: int, length: float) -> List[Arrival]:
    """Realization ``k`` of one period, due times from its start."""
    rng = rng_for(mix["draw_seed"], k)
    due: List[float] = []
    for start, span, rate in phases(mix, length):
        due += list(start + np.sort(rng.uniform(0.0, span, rng.poisson(rate * span))))
    n = len(due)
    prompts = lengths(mix["prompt"], n, rng)
    outputs = lengths(mix["output"], n, rng)
    names, shares = zip(*sorted(mix["tenants"].items()))
    tenants = rng.choice(np.array(names), n, p=np.array(shares) / sum(shares))
    return [Arrival(float(t), int(p), int(o), str(tn))
            for t, p, o, tn in zip(due, prompts, outputs, tenants)]


def schedule(mix: Dict, seed: int, seconds: float) -> List[Arrival]:
    """The requests due in a window of ``seconds``, in order of due time:
    the mix's realizations of its periods, in the order ``seed`` sets."""
    P = period_s(mix, seconds)
    K = int(math.ceil(seconds / P))
    draws = [draw_period(mix, k, P) for k in range(K)]
    out: List[Arrival] = []
    for j, k in enumerate(rng_for(seed, 0).permutation(K)):
        out += [replace(a, due_s=j * P + a.due_s) for a in draws[k] if j * P + a.due_s < seconds]
    return sorted(out, key=lambda a: a.due_s)


def prompt_tokens(seed: int, arrivals: List[Arrival], vocab: int) -> List[np.ndarray]:
    rng = rng_for(seed, 1)
    return [rng.integers(0, vocab, a.prompt_len, dtype=np.int32) for a in arrivals]
