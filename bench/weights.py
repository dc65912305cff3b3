"""Random weights for a cell, drawn on the device in one jitted call.

The tree has the program's parameter layout (``param_structs`` gives the
shapes); the values are the benchmark's own: embeddings N(0, 1), every
matrix N(0, 1/fan_in), norm scales 0. The same seed gives the same
weights, in the dtype the configuration serves.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Leaf name -> index (from the end) of the axes that are contracted when
# the leaf is applied; fan_in is their product. ``None``: a norm scale (0).
_FAN_IN_AXES = {
    "tok": (),                      # embedding rows: unit variance
    "wq": (-3,), "wk": (-3,), "wv": (-3,),
    "wo": (-3, -2),
    "w_up": (-2,), "w_gate": (-2,), "w_down": (-2,), "w": (-2,),
    "router": (-2,),
    "shared_gate": (-2,), "shared_up": (-2,), "shared_down": (-2,),
    "ln": None, "scale": None, "q_norm": None, "k_norm": None,
}


def key_from_seed(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1] & 0x7FFFFFFF))


def leaf_scale(name: str, shape) -> float:
    if name not in _FAN_IN_AXES:
        raise KeyError(f"no weight rule for parameter {name!r}")
    axes = _FAN_IN_AXES[name]
    if axes is None:
        return 0.0
    fan_in = int(np.prod([shape[a] for a in axes])) if axes else 1
    return fan_in ** -0.5


def make_fn(structs):
    """The draw as a function of a PRNG key: ``structs`` is a pytree of
    ShapeDtypeStruct ({group: {name: struct}})."""
    flat = [(g, n, s) for g, grp in sorted(structs.items())
            for n, s in sorted(grp.items())]

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = {}
        for (g, n, s), k in zip(flat, keys):
            scale = leaf_scale(n, s.shape)
            if scale == 0.0:
                leaf = jnp.zeros(s.shape, s.dtype)
            else:
                leaf = (jax.random.normal(k, s.shape, s.dtype)
                        * jnp.asarray(scale, s.dtype))
            out.setdefault(g, {})[n] = leaf
        return out

    return make


def draw(structs, seed: int):
    """The weights as device arrays, made by one jitted call."""
    return jax.jit(make_fn(structs))(key_from_seed(seed))
