"""Plain reference of a pre-norm decoder with clustered head attention.

It covers both configurations: rotary multi-head or grouped-query
attention, a dense (gated or squared-ReLU) or a mixture-of-experts FFN
with shared experts, RMSNorm with (1 + scale), untied output head. It is
written from the layer equations in straightforward ``jax.numpy``, in
float32 with every matrix product at ``Precision.HIGHEST``, and imports
nothing of the program. It takes only the configuration's sizes, the
weights the benchmark drew, and what was served: each request's tokens
and the head -> representative map its CHAI membership defines.

Clustered head attention (CHAI, arXiv:2403.08058): the ``warmup``
decode positions after the prompt attend as plain MHA / GQA, and each
head's attention probabilities over the first ``feature_window`` keys,
summed over those positions, are its features. Per layer, the heads are
clustered by K-Means on the features standardised per head (squared
distance = 2 (1 - Pearson correlation)), within each KV group for GQA;
the member nearest its cluster's centre represents it. From decode
position ``chai_from`` on, head h attends with the scores of its
representative r(h) (the representative's query against its keys) and
weighs its own values. The reference clusters from its own features; a
run may pass another head -> representative map to apply in its place.

``control=True`` computes every matrix product with both operands rounded
to float8 e4m3 under a per-tensor scale: the same reference one precision
step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _f8(x):
    """Round to float8 e4m3 under a per-tensor scale; back in float32."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec, a, b, control=False):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if control:
        a, b = _f8(a), _f8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def rope(x, pos, theta):
    """Half-rotation rotary embedding. x: (N, heads, hd); pos: (N,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def act(name, x):
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    if name == "relu2":
        return jnp.square(jnp.maximum(x, 0.0))
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


def kmeans_reps(z, k, iters):
    """z: (n, F) standardised features. Farthest-point initial centres,
    ``iters`` Lloyd steps, then the member nearest each centre represents
    its cluster. Distances are exact differences, no matrix product.
    Returns the representative of every point, (n,) int32."""
    n = z.shape[0]

    def d2(c):
        return jnp.sum(jnp.square(z[:, None, :] - c[None, :, :]), -1)

    first = jnp.argmax(jnp.sum(jnp.square(z - z.mean(0)), -1))
    centres = jnp.zeros((k, z.shape[1]), z.dtype).at[0].set(z[first])
    mind = jnp.sum(jnp.square(z - z[first]), -1)
    for i in range(1, k):
        nxt = jnp.argmax(mind)
        centres = centres.at[i].set(z[nxt])
        mind = jnp.minimum(mind, jnp.sum(jnp.square(z - z[nxt]), -1))

    def lloyd(_, c):
        onehot = jax.nn.one_hot(jnp.argmin(d2(c), -1), k, dtype=z.dtype)
        cnt = onehot.sum(0)[:, None]
        sums = jnp.sum(onehot.T[:, :, None] * z[None], axis=1)
        return jnp.where(cnt > 0, sums / jnp.maximum(cnt, 1.0), c)

    centres = jax.lax.fori_loop(0, iters, lloyd, centres)
    d = d2(centres)
    assign = jnp.argmin(d, -1)
    member = jax.nn.one_hot(assign, k, dtype=jnp.bool_)
    rep = jnp.argmin(jnp.where(member, d, jnp.inf), axis=0)
    return rep[assign].astype(jnp.int32)


def standardise(f):
    f = f - f.mean(-1, keepdims=True)
    return f / jnp.maximum(jnp.sqrt(jnp.sum(jnp.square(f), -1,
                                            keepdims=True)), 1e-12)


def cluster(sw, warm_from, sz):
    """Head -> representative map from the plain attention scores ``sw``
    (H, warmup, N) of this layer's warm-up rows, which start at position
    ``warm_from``. Returns (rep (H,), standardised features (H, F))."""
    h, kv = sz["n_heads"], sz["n_kv_heads"]
    w, wf = sz["warmup"], sz["feature_window"]
    pos = jnp.arange(sw.shape[-1])
    keep = pos[None, :] <= (warm_from + jnp.arange(w))[:, None]
    prob = jax.nn.softmax(jnp.where(keep[None], sw, -jnp.inf), axis=-1)
    z = standardise(prob[:, :, :wf].sum(1))                   # (H, F)
    if h == kv:
        return kmeans_reps(z, sz["k_max"], sz["kmeans_iters"]), z
    g = h // kv
    k = min(-(-sz["k_max"] // kv), g)
    local = jax.vmap(lambda zg: kmeans_reps(zg, k, sz["kmeans_iters"]))(
        z.reshape(kv, g, -1))
    return (local + (jnp.arange(kv) * g)[:, None]).reshape(h), z


def attention(x, p, given_rep, use_given, n_valid, chai_from, sz, control,
              qblk):
    """x: (N, d) normed input of one layer; p: that layer's weights. Rows
    at positions >= chai_from attend with their representative head's
    scores: the reference's own clustering, or ``given_rep`` (H,) where
    ``use_given``. Returns ((N, d), own rep (H,), features (H, F))."""
    n = x.shape[0]
    h, kv, hd = sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]
    g = h // kv
    pos = jnp.arange(n, dtype=jnp.int32)
    q = rope(mm("nd,dhe->nhe", x, p["wq"], control), pos, sz["rope_theta"])
    k = rope(mm("nd,dke->nke", x, p["wk"], control), pos, sz["rope_theta"])
    v = mm("nd,dke->nke", x, p["wv"], control)
    kv_of_head = jnp.arange(h) // g
    kh = k[:, kv_of_head]                               # (N, H, hd)
    vh = v[:, kv_of_head]
    key_ok = pos < n_valid

    if sz["k_max"]:
        warm_from = chai_from - sz["warmup"]
        qw = jax.lax.dynamic_slice_in_dim(q, warm_from, sz["warmup"], 0)
        sw = mm("qhe,nhe->hqn", qw, kh, control) / math.sqrt(hd)
        own_rep, feats = cluster(sw, warm_from, sz)
    else:                                   # no CHAI: every head its own
        own_rep, feats = jnp.arange(h, dtype=jnp.int32), jnp.zeros((h, 1))
    rep_of_head = jnp.where(use_given, given_rep, own_rep)

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qblk, qblk, 0)
        qpos = i * qblk + jnp.arange(qblk, dtype=jnp.int32)
        s = mm("qhe,nhe->hqn", qs, kh, control) / math.sqrt(hd)
        s_rep = s[rep_of_head]                          # rep's scores
        chai = (qpos >= chai_from)[None, :, None]
        s = jnp.where(chai, s_rep, s)
        mask = (pos[None, :] <= qpos[:, None]) & key_ok[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        prob = jax.nn.softmax(s, axis=-1)
        return mm("hqn,nhe->qhe", prob, vh, control)

    out = jax.lax.map(block, jnp.arange(n // qblk)).reshape(n, h, hd)
    return mm("nhe,hed->nd", out, p["wo"], control), own_rep, feats


def dense_ffn(x, p, sz, control):
    u = mm("nd,df->nf", x, p["w_up"], control)
    if sz["gated_mlp"]:
        u = act(sz["activation"], mm("nd,df->nf", x, p["w_gate"],
                                     control)) * u
    else:
        u = act(sz["activation"], u)
    return mm("nf,fd->nd", u, p["w_down"], control)


def moe_ffn(x, p, sz, control):
    """Top-k softmax routing with the top-k weights renormalised to 1 (the
    repo's stated departure), every expert evaluated densely and weighted
    by its gate (0 off the top-k), plus the shared experts."""
    e, k = sz["n_experts"], sz["top_k"]
    logits = mm("nd,de->ne", x, p["router"], control)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)      # (N, E)

    def one(acc, j):
        gj = mm("nd,df->nf", x, p["w_gate"][j], control)
        uj = mm("nd,df->nf", x, p["w_up"][j], control)
        yj = mm("nf,fd->nd", act(sz["activation"], gj) * uj,
                p["w_down"][j], control)
        return acc + gates[:, j, None] * yj, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(e))
    if sz.get("n_shared_experts", 0):
        sg = mm("nd,df->nf", x, p["shared_gate"], control)
        su = mm("nd,df->nf", x, p["shared_up"], control)
        y = y + mm("nf,fd->nd", act(sz["activation"], sg) * su,
                   p["shared_down"], control)
    return y


def _layer(x, attn_p, ffn_p, given_rep, use_given, n_valid, chai_from, *,
           sz, kind, control, qblk):
    eps = sz["norm_eps"]
    y, own_rep, feats = attention(rms_norm(x, attn_p["ln"], eps), attn_p,
                                  given_rep, use_given, n_valid, chai_from,
                                  sz, control, qblk)
    x = x + y
    xn = rms_norm(x, ffn_p["ln"], eps)
    y = moe_ffn(xn, ffn_p, sz, control) if kind == "moe" \
        else dense_ffn(xn, ffn_p, sz, control)
    return x + y, own_rep, feats


def _head(x, rows, final_scale, w, *, eps, control):
    h = rms_norm(x[rows], final_scale, eps)
    return mm("nd,dv->nv", h, w, control)


@functools.lru_cache(maxsize=None)
def _jits(sz_items, control, qblk):
    sz = dict(sz_items)
    layer = {kind: jax.jit(functools.partial(
        _layer, sz=sz, kind=kind, control=control, qblk=qblk))
        for kind in ("dense", "moe")}

    def head_stats(x, rows, served, final_scale, w):
        lg = _head(x, rows, final_scale, w, eps=sz["norm_eps"],
                   control=control)
        return (lg.max(-1), jnp.take_along_axis(lg, served, 1),
                jnp.argmax(lg, -1).astype(jnp.int32))

    return layer, jax.jit(head_stats)


def _index(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def served_logits(params, sizes, tokens, n_prompt, served, given_rep=None,
                  *, control=False, bucket=1024, qblk=512):
    """Run the reference over ``tokens`` (prompt + served tokens but the
    last) and read, at each position that produced a served token, the
    reference's best logit, the logits of the tokens in ``served`` (one
    column per token set: (m,) or (m, c)) and its own argmax.

    ``sizes`` holds the configuration's sizes and its CHAI settings
    (``warmup``, ``feature_window``, ``k_max``, ``kmeans_iters``). The
    reference clusters the heads itself, unless ``given_rep`` ((layers, H)
    int32) is passed. Returns numpy (best (m,), logits of ``served`` in its
    shape, argmax (m,), own representative map (layers, H), standardised
    features (layers, H, F)). Requires more than ``warmup`` served tokens
    when CHAI is on."""
    sz = {k: v for k, v in sizes.items() if not isinstance(v, (list, dict))}
    served = np.asarray(served, np.int32)
    cols = served.reshape(len(served), -1)
    n_real = len(tokens)
    n = -(-n_real // bucket) * bucket
    toks = np.zeros((n,), np.int32)
    toks[:n_real] = tokens
    ffn_types = sizes.get("ffn_types") or \
        ["moe" if sizes.get("n_experts") else "dense"] * sizes["n_layers"]
    layer, head = _jits(tuple(sorted(sz.items())), control,
                        min(qblk, n))
    x = params["embed"]["tok"][jnp.asarray(toks)].astype(jnp.float32)
    chai_from = jnp.int32(n_prompt + sz["warmup"])
    n_valid = jnp.int32(n_real)
    use_given = jnp.bool_(given_rep is not None)
    if given_rep is None:
        given_rep = np.zeros((len(ffn_types), sz["n_heads"]), np.int32)
    counts = {"dense": 0, "moe": 0}
    reps, feats = [], []
    for i, kind in enumerate(ffn_types):
        grp = "moe" if kind == "moe" else "ffn"
        x, r, f = layer[kind](x, _index(params["attn"], i),
                              _index(params[grp], counts[kind]),
                              jnp.asarray(given_rep[i], jnp.int32),
                              use_given, n_valid, chai_from)
        reps.append(r)
        feats.append(f)
        counts[kind] += 1
    m = len(served)
    m_pad = -(-m // 256) * 256          # one compiled head per 256 rows
    rows = np.full((m_pad,), n_prompt - 1, np.int32)
    rows[:m] = n_prompt - 1 + np.arange(m)
    want = np.zeros((m_pad, cols.shape[1]), np.int32)
    want[:m] = cols
    best, got, top = head(x, jnp.asarray(rows), jnp.asarray(want),
                          params["final_norm"]["scale"],
                          params["unembed"]["w"])
    got = np.asarray(got)[:m].reshape(served.shape)
    return (np.asarray(best)[:m], got, np.asarray(top)[:m],
            np.stack([np.asarray(r) for r in reps]),
            np.stack([np.asarray(f) for f in feats]))
