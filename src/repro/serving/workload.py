"""Open-loop serving workload generators (shared by examples + benches).

One definition of the "mixed-length Poisson" workload so the latency
benchmark and the serving example measure the same distribution:
exponential inter-arrival gaps and a long-tailed output-length mix —
most requests short, a minority near the cap, the regime where lockstep
(cohort) scheduling would head-of-line block short requests behind long
ones.
"""
from __future__ import annotations

import numpy as np


def poisson_workload(rng, n_req, *, mean_gap_s=0.02, new_tokens=(8, 128),
                     tail_frac=0.3):
    """Returns (arrival_delays (n,), max_new_tokens (n,)) numpy arrays.

    ``new_tokens = (lo, hi)``: short requests draw from [lo, lo+8],
    long ones (fraction ``tail_frac``) from [hi-28, hi].
    """
    arrivals = np.cumsum(rng.exponential(mean_gap_s, size=n_req))
    lo, hi = new_tokens
    lens = np.where(rng.random(n_req) >= tail_frac,
                    rng.integers(lo, lo + 9, size=n_req),
                    rng.integers(hi - 28, hi + 1, size=n_req))
    return arrivals, lens
