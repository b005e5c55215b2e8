"""The one general traffic generator. A traffic mix is a data file under
``benchmark/traffic/``; this module turns (file, seed, window) into the
requests a run sends. No JAX: the load generator's child imports it.

Seed discipline: the SET of sizes and of gaps between arrivals is drawn from
the file's ``shape_seed`` and is the same for every ``--seed``; the run's
seed only puts them in another order and draws the prompt token ids. Runs
with different seeds then do the same work, and a difference between them is
noise of the system and not of the draw.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

# ids below this are the byte tokenizer's bytes and its BOS/EOS specials
_FIRST_PLAIN_ID = 258


def load(path: str | Path) -> dict:
    return json.loads(Path(path).read_text())


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from ``{"dist": "lognormal", "median", "sigma", "min",
    "max"}`` or ``{"dist": "fixed", "value"}``."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def draw_gaps(spec: dict, horizon_s: float, rng: np.random.Generator
              ) -> np.ndarray:
    """Gaps between arrivals of a renewal process at ``rate_per_s`` whose
    gaps have coefficient of variation ``cv`` (1 = Poisson, above 1 =
    bursty, gamma-distributed). The count is fixed at rate * horizon and the
    gaps are scaled to fill the horizon exactly, so every order of them
    offers the same load."""
    n = max(int(round(spec["rate_per_s"] * horizon_s)), 1)
    cv = float(spec.get("cv", 1.0))
    gaps = rng.gamma(1.0 / cv ** 2, cv ** 2, size=n)
    return gaps * (horizon_s / gaps.sum())


def _prompts(lengths, shared: list, vocab: int, rng) -> list:
    return [shared[:int(n)] + rng.integers(
        _FIRST_PLAIN_ID, vocab, size=max(int(n) - len(shared), 0)).tolist()
        for n in lengths]


def requests(traffic: dict, seed: int, horizon_s: float, vocab: int) -> list:
    """The requests of one run, as dicts ``{"due", "prompt", "max_tokens"}``
    in sending order. Open loop (``serve-open``): ``due`` is seconds from
    the start of the traffic. Closed loop (``serve-closed``): ``due`` is
    None and the clients take the requests in order; the list is long
    enough for ``pool_per_client`` requests a client."""
    shape = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    order = np.random.default_rng(int(seed))
    if traffic["kind"] == "serve-open":
        gaps = draw_gaps(traffic["arrivals"], horizon_s, shape)
        n = len(gaps)
        # the gaps sum to the horizon: a microsecond earlier, so that the
        # last arrival falls inside it
        due = np.cumsum(order.permutation(gaps)) - 1e-6
    elif traffic["kind"] == "serve-closed":
        n = int(traffic["clients"]) * int(traffic.get("pool_per_client", 64))
        due = [None] * n
    else:
        raise ValueError(f"not a serving mix: kind {traffic['kind']!r}")
    p_len = draw_lengths(traffic["prompt_tokens"], n, shape)
    o_len = draw_lengths(traffic["output_tokens"], n, shape)
    shared = shape.integers(_FIRST_PLAIN_ID, vocab, size=int(
        traffic.get("shared_prefix_tokens", 0))).tolist()
    pairs = order.permutation(n)
    prompts = _prompts(p_len[pairs], shared, vocab, order)
    return [{"due": None if d is None else float(d), "prompt": p,
             "max_tokens": int(o)}
            for d, p, o in zip(due, prompts, o_len[pairs])]


def describe(reqs: list) -> dict:
    """The drawn distribution, for the run's log and the tests."""
    def five(xs):
        q = np.percentile(xs, [0, 50, 95, 100])
        return {"n": len(xs), "min": float(q[0]), "p50": float(q[1]),
                "p95": float(q[2]), "max": float(q[3]),
                "sum": float(np.sum(xs))}
    out = {"prompt_tokens": five([len(r["prompt"]) for r in reqs]),
           "output_tokens": five([r["max_tokens"] for r in reqs])}
    dues = [r["due"] for r in reqs if r["due"] is not None]
    if len(dues) > 1:
        out["gap_s"] = five(np.diff([0.0] + dues))
    return out
