"""One generator for every traffic mix (``bench/traffic/<mix>.json``).

Sizes do not depend on ``--seed``. A mix holds a pool of ``pool`` request
sizes (prompt and answer lengths, and for open loops the gaps between
arrivals) taken at evenly spaced quantiles of the mix's distributions, and
serves them in one fixed order, so every seed sends the same sizes at the
same points of the run. The seed draws every token id. A run sends a few
tens of requests, a small part of the pool: were the order drawn from the
seed, the seed would choose which sizes fall in the window, and so the
work of the run.

Mix parameters:

    loop           "closed" (a client sends its next request when its last
                   one finishes) or "open" (requests are due on a schedule)
    clients        closed loop: "slots" (one client per batch slot) or a count
    rate_rps       open loop: mean arrival rate, Poisson arrivals
    pool           number of request sizes in the pool
    prompt/output  {"dist": "uniform" | "loguniform" | "lognormal",
                    "lo", "hi", ["median", "sigma"]}: token lengths
    shared_prefix  {"count", "tokens"}: that many system prompts of that
                   length; client c holds prompt c % count and ``prompt``
                   draws the length of a fresh suffix
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``dist``, clipped to
    [lo, hi]."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["lo"]), int(dist["hi"])
    kind = dist["dist"]
    if kind == "uniform":
        x = lo + u * (hi - lo)
    elif kind == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of Exp(rate)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def fixed_order(n: int, stream: int) -> list:
    """A shuffle of ``range(n)`` that is the same in every run, taken from
    its end (``pop``)."""
    return np.random.default_rng(stream).permutation(n).tolist()


class Traffic:
    """Requests of one mix for one seed.

    ``next_request(client)`` -> (prompt int32 array, max_new_tokens, meta)
    serves closed loops; ``schedule(seconds)`` -> [(due_offset_s, prompt,
    max_new, meta)] serves open loops.
    """

    def __init__(self, mix: dict, seed: int, vocab: int, slots: int,
                 scale: float = 1.0):
        self.loop = mix["loop"]
        self.scale = scale
        self.vocab = int(vocab)
        self.rng = np.random.default_rng(seed)
        n = int(mix.get("pool", 256))
        p_dist, o_dist = dict(mix["prompt"]), dict(mix["output"])
        if scale != 1.0:            # CPU rehearsal: shrink every length
            for d in (p_dist, o_dist):
                for key in ("lo", "hi", "median"):
                    if key in d:
                        d[key] = max(2, int(round(d[key] * scale)))
        prompts = quantile_lengths(p_dist, n)
        outputs = quantile_lengths(o_dist, n)
        # Pair prompt and answer quantiles by a fixed shuffle, so the pool
        # (not just its margins) is the same for every seed.
        outputs = outputs[np.random.default_rng(0).permutation(n)]
        self.sizes = list(zip(prompts.tolist(), outputs.tolist()))
        self.gaps = (exponential_gaps(float(mix["rate_rps"]), n)
                     if self.loop == "open" else None)
        self._order = []
        self._gap_order = []
        self.clients = (slots if mix.get("clients", "slots") == "slots"
                        else int(mix["clients"]))
        sp = mix.get("shared_prefix")
        self.prefixes = []
        if sp:
            t = max(2, int(round(int(sp["tokens"]) * scale)))
            self.prefixes = [self._tokens(t) for _ in range(int(sp["count"]))]

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=int(n)).astype(np.int32)

    def _next_size(self):
        if not self._order:
            self._order = fixed_order(len(self.sizes), 1)
        return self.sizes[self._order.pop()]

    def next_request(self, client: int):
        p_len, out = self._next_size()
        meta = {"prefix": -1, "prefix_len": 0}
        if self.prefixes:
            k = client % len(self.prefixes)
            prompt = np.concatenate([self.prefixes[k], self._tokens(p_len)])
            meta = {"prefix": k, "prefix_len": len(self.prefixes[k])}
        else:
            prompt = self._tokens(p_len)
        return prompt, int(out), meta

    def schedule(self, seconds: float):
        """Open loop: every request due within ``seconds``, with its due
        time as an offset from the start of the window."""
        out, t = [], 0.0
        while True:
            if not self._gap_order:
                self._gap_order = fixed_order(len(self.gaps), 2)
            t += float(self.gaps[self._gap_order.pop()])
            if t > seconds:
                return out
            prompt, max_new, meta = self.next_request(len(out))
            out.append((t, prompt, max_new, meta))

    def max_lengths(self):
        """(longest prompt, longest answer) this mix can send."""
        pre = max((len(p) for p in self.prefixes), default=0)
        return (pre + max(p for p, _ in self.sizes),
                max(o for _, o in self.sizes))

    def prompt_lengths(self):
        pre = max((len(p) for p in self.prefixes), default=0)
        return sorted({pre + p for p, _ in self.sizes})
