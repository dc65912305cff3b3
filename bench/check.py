"""Decide ``correct``: the served tokens against the plain reference.

After the window closes, a sample of the requests the window finished,
drawn from the seed and holding the longest of them, is run through the
configuration's reference over each prompt with its served tokens. Three
numbers are compared:

- ``membership_excess``: how much worse the run's CHAI membership, as it
  chose it at its CLUSTER transition, approximates the heads than the
  reference's own clustering does. The reference clusters the heads itself
  from its own warm-up features; per layer, the cost of a membership is
  the sum over heads of the squared distance between a head's
  standardised features and its representative's. The number is the
  mean, over requests and layers, of the run's cost over the reference's,
  less 1 (0 where they agree; below 0 where the run's K-Means found the
  better optimum). The cost is blind to which member of a two-head cluster
  represents it, a tie that no reference can break as the run did;
  anything else that worsens the approximation raises it. The largest
  ratio is printed beside it: one layer's K-Means can settle in another
  local optimum under bf16 features, which moves the largest by a few
  hundredths on a sound run and the mean by a thousandth.
- ``membership_faults``: entries that break the partition of the heads.
- ``gap_mean``: the mean, over every served token compared, of the gap by
  which its logit lies below the reference's best logit at that position
  (greedy traffic, so a sound run serves the reference's argmax up to
  rounding). From the first STEADY position on, the reference applies the
  run's membership, once the two numbers above have judged it. The widest
  gap, which rounding alone sets among near-tied logits, is printed beside
  it.
"""
from __future__ import annotations

import numpy as np


def rep_of_head(mem, sizes) -> np.ndarray:
    """(layers, H) representative head of every head, from a membership
    (MHA: h2c (L, H), reps (L, k); GQA: cluster_of (L, KV, g), reps
    (L, KV, r)). ``mem=None`` (never clustered): every head is its own."""
    h, kv = sizes["n_heads"], sizes["n_kv_heads"]
    n_layers = sizes["n_layers"]
    if mem is None:
        return np.tile(np.arange(h, dtype=np.int32), (n_layers, 1))
    if "h2c" in mem:
        h2c, reps = mem["h2c"], mem["reps"]
        return np.take_along_axis(reps, h2c, axis=1).astype(np.int32)
    co, reps = mem["cluster_of"], mem["reps"]
    g = h // kv
    local = np.take_along_axis(reps, co, axis=2)           # (L, KV, g)
    base = (np.arange(kv) * g)[None, :, None]
    return (base + local).reshape(n_layers, h).astype(np.int32)


def membership_faults(mem) -> int:
    """Entries that break the partition: a head pointing past the cluster
    count, or a used cluster whose representative is not its member."""
    if mem is None:
        return 0
    if "h2c" in mem:
        assign, reps = mem["h2c"][:, None, :], mem["reps"][:, None, :]
    else:
        assign, reps = mem["cluster_of"], mem["reps"]
    k = reps.shape[-1]
    bad = int((assign < 0).sum() + (assign >= k).sum())
    a = np.clip(assign, 0, k - 1)
    for idx in np.ndindex(*a.shape[:-1]):
        row, rp = a[idx], reps[idx]
        for c in np.unique(row):
            r = int(rp[c])
            if not (0 <= r < row.shape[0]) or row[r] != c:
                bad += 1
    return bad


def sample(finished, seed: int, k: int):
    """The request with the most served tokens, and up to ``k - 1`` more
    drawn from the seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r["generated"]), r["uid"]))
    rest = order[1:]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [order[0]] + [rest[i] for i in sorted(pick)]


def rep_cost(feats, rep):
    """Per layer, the sum over heads of |z_h - z_rep(h)|^2. feats: (L, H,
    F) standardised; rep: (L, H)."""
    zr = np.take_along_axis(feats, rep[:, :, None].astype(np.int64), axis=1)
    return np.square(feats - zr).sum(-1).sum(-1)


def compare(reference, params, sizes, requests, *, control=False):
    """Per request: a dict of ``gap`` (the served tokens' gaps, (m,)) and
    ``cost`` (the run's membership's cost ratio per layer, or None where
    the request never clustered). With ``control``, also ``gap_control``
    and ``cost_control``: the control is the reference one precision step
    lower, clustering from its own features, in the program's place; its
    tokens and membership are judged as the run's are."""
    out = []
    for r in requests:
        served = np.asarray(r["generated"], np.int32)
        toks = np.concatenate([np.asarray(r["prompt"], np.int32),
                               served[:-1]])
        args = (params, sizes, toks, len(r["prompt"]))
        mem = r.get("membership")
        given = None if mem is None else rep_of_head(mem, sizes)
        best, got, _, rep_ref, feats = reference.served_logits(
            *args, served, given)
        ref_cost = np.maximum(rep_cost(feats, rep_ref), 1e-9)
        res = {"gap": best - got, "cost": None}
        if given is not None:
            res["cost"] = rep_cost(feats, given) / ref_cost
            res["agree"] = float(np.mean(given == rep_ref))
        if control:
            _, _, top, rep_c, _ = reference.served_logits(*args, served,
                                                          control=True)
            bc, gc, *_ = reference.served_logits(*args, top, rep_c)
            res["gap_control"] = bc - gc
            if given is not None:
                res["cost_control"] = rep_cost(feats, rep_c) / ref_cost
        out.append(res)
    return out
