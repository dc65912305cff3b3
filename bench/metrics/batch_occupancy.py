"""Mean share of batch slots that decoded in each batched decode step of
the window, in %: decode tokens emitted / (decode steps x slots). Decode
tokens are the window's tokens less its first tokens (those come from
prefill)."""


def read(ctx):
    s0, s1 = ctx["stats"]
    steps = s1["steps_executed"] - s0["steps_executed"]
    ws = ctx["window_stats"]
    if steps <= 0:
        return None
    decoded = ws["tokens"] - ws["first_tokens"]
    return 100.0 * decoded / (steps * ctx["ecfg"].batch_slots)
