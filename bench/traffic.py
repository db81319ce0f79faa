"""The one traffic generator: a mix file of parameters -> requests.

A mix (``bench/traffic/<name>.json``) gives

  loop          "open" (arrivals on a schedule) or "closed" (clients that
                each send their next request when the last one finished)
  rate_per_s    open loop: mean Poisson arrival rate
  clients_per_slot   closed loop: clients per engine slot
  prompt        {"dist": "lognormal", "median", "sigma", "min", "max"}
                or {"dist": "uniform", "min", "max"} (tokens)
  output        the same, or {"dist": "fill"}: the rest of max_seq after
                the prompt
  warmup_s      seconds the traffic runs before the window opens

Every seed sends the same set of sizes and arrival gaps, in an order
drawn from the seed, with its own token ids: sizes are the
distribution's quantiles at evenly spaced probabilities and gaps the
exponential's, so the seed changes which request comes when, not how
much work there is. In an open loop the warm-up and the window each get
their own set, the gaps scaled to fill them: the window always holds
rate x seconds arrivals.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Item:
    """One request to send: ``due`` is seconds after the traffic starts
    (open loop), or None (closed loop: due when its client is free)."""
    prompt: np.ndarray
    max_new_tokens: int
    due: float | None = None


def _quantiles(dist: dict, n: int) -> np.ndarray:
    p = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in p])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + p * (dist["max"] + 1 - dist["min"])
    else:
        raise ValueError(f"unknown size distribution {dist['dist']!r}")
    return np.clip(np.floor(v), dist["min"], dist["max"]).astype(np.int64)


def _sizes(mix: dict, n: int, max_seq: int, rng) -> tuple[np.ndarray, ...]:
    prompts = rng.permutation(_quantiles(mix["prompt"], n))
    if mix["output"]["dist"] == "fill":
        outs = max_seq - prompts
    else:
        outs = rng.permutation(_quantiles(mix["output"], n))
    if np.any(prompts + outs > max_seq):
        raise ValueError("a request of the mix exceeds max_seq")
    return prompts, outs


def requests_needed(mix: dict, seconds: float, clients: int) -> int:
    """How many requests to draw so the traffic cannot run dry."""
    if mix["loop"] == "open":
        return sum(_arrivals(mix, seconds))
    return clients * mix.get("requests_per_client", 64)


def _arrivals(mix: dict, seconds: float) -> tuple[int, int]:
    """Arrivals in the warm-up and in the window of an open loop."""
    rate = mix["rate_per_s"]
    return (int(round(rate * mix.get("warmup_s", 0))),
            max(1, int(round(rate * seconds))))


def _open_items(mix, n, t0, span, max_seq, vocab, rng) -> list[Item]:
    """``n`` requests due in [t0, t0 + span), gaps exponential quantiles
    scaled to fill the span."""
    if n == 0:
        return []
    prompts, outs = _sizes(mix, n, max_seq, rng)
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    due = t0 + span * (np.cumsum(gaps) - gaps) / gaps.sum()
    return [Item(rng.integers(1, vocab, int(p)).astype(np.int32), int(o),
                 float(t)) for p, o, t in zip(prompts, outs, due)]


def generate(mix: dict, seed: int, *, seconds: float, clients: int,
             max_seq: int, vocab: int) -> list[Item] | list[list[Item]]:
    """Open loop: one list of Items with due times (seconds after the
    traffic starts; the window opens after ``warmup_s``). Closed loop:
    one list of Items per client."""
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        warm = mix.get("warmup_s", 0)
        n_warm, n_win = _arrivals(mix, seconds)
        return (_open_items(mix, n_warm, 0.0, warm, max_seq, vocab, rng)
                + _open_items(mix, n_win, warm, seconds, max_seq, vocab,
                              rng))
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = requests_needed(mix, seconds, clients)
    prompts, outs = _sizes(mix, n, max_seq, rng)
    items = [Item(rng.integers(1, vocab, int(p)).astype(np.int32), int(o))
             for p, o in zip(prompts, outs)]
    return [items[i::clients] for i in range(clients)]
