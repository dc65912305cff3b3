#!/usr/bin/env python3
"""Run a benchmark cell with its timed path broken underneath.

    python3 bench/faults.py --fault <name> --workload <cell> --seed <n> ...

Each fault is planted in the program before the run builds its engine; the
run then goes on as ``bench/run.py`` does and must come out not correct.
The tests run every fault at a tiny size (``--rehearse``); on the chip the
same command reads a fault at the cell's own size, which sets the upper
reading of the number that it moves.

- ``state_unchanged``: the batched decode steps return their state
  unchanged;
- ``half_batch``: the decode steps' second half of the batch gets the
  first half's logits;
- ``token_altered``: every greedy token is moved to its neighbour id;
- ``permuted_membership``: every CHAI membership is relabelled by a
  rotation of the heads, which leaves a valid partition that clusters the
  wrong heads together.
"""
from __future__ import annotations

import sys
from pathlib import Path

FAULTS = ("state_unchanged", "half_batch", "token_altered",
          "permuted_membership")


def _wrap_steps(setattr_, fault):
    from repro.launch import steps as steps_mod
    for maker in ("make_mixed_step", "make_serve_step"):
        orig = getattr(steps_mod, maker)

        def patched(*a, _orig=orig, **k):
            f = _orig(*a, **k)

            def step(params, inputs, state, *rest):
                logits, new = f(params, inputs, state, *rest)
                return fault(logits, state, new)
            return step
        setattr_(steps_mod, maker, patched)


def _state_unchanged(logits, old, new):
    return logits, old


def _half_batch(logits, old, new):
    import jax.numpy as jnp
    h = logits.shape[0] // 2
    return jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]), new


def _permute(ctx):
    """Head h takes the cluster of head h + 1; each representative moves
    with it, so the partition stays valid."""
    import jax.numpy as jnp
    if "h2c" in ctx:
        h = ctx["h2c"].shape[-1]
        return {"h2c": jnp.roll(ctx["h2c"], -1, axis=-1),
                "reps": (ctx["reps"] - 1) % h}
    g = ctx["cluster_of"].shape[-1]
    return {"cluster_of": jnp.roll(ctx["cluster_of"], -1, axis=-1),
            "reps": (ctx["reps"] - 1) % g}


def plant(name, setattr_=setattr):
    """Plant fault ``name``. ``setattr_`` lets a test undo it
    (``monkeypatch.setattr``)."""
    import jax
    import jax.numpy as jnp
    from repro.core import clustering
    from repro.serving import engine as engine_mod
    if name == "state_unchanged":
        _wrap_steps(setattr_, _state_unchanged)
    elif name == "half_batch":
        _wrap_steps(setattr_, _half_batch)
    elif name == "token_altered":
        orig = engine_mod.EngineCore.__init__

        def init(self, *a, **k):
            orig(self, *a, **k)
            self._argmax = jax.jit(
                lambda lg: (jnp.argmax(lg, -1).astype(jnp.int32) + 1)
                % lg.shape[-1])
        setattr_(engine_mod.EngineCore, "__init__", init)
    elif name == "permuted_membership":
        orig_id = clustering.identify_membership
        setattr_(clustering, "identify_membership",
                 lambda sc, cfg: _permute(orig_id(sc, cfg)))
    else:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--fault" not in argv:
        raise SystemExit(f"--fault <name> is required; one of {FAULTS}")
    i = argv.index("--fault")
    name = argv[i + 1]
    del argv[i:i + 2]
    root = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(root / "src"), str(root)]
    if "--rehearse" in argv:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    from bench import run
    plant(name)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
