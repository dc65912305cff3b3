"""Share of admitted prompt tokens served from the radix prefix cache in
the window, in %: ``tokens_reused`` / (``tokens_reused`` +
``tokens_prefilled``), from the engine's prefix-cache counters."""


def read(ctx):
    s0, s1 = ctx["stats"]
    if "prefix.tokens_reused" not in s1:
        return None
    reused = s1["prefix.tokens_reused"] - s0["prefix.tokens_reused"]
    filled = s1["prefix.tokens_prefilled"] - s0["prefix.tokens_prefilled"]
    if reused + filled <= 0:
        return None
    return 100.0 * reused / (reused + filled)
