"""95th percentile time to first token in the closed-loop decode cell, in
ms, from when each request was sent. Too few first tokens fall in one
window for it to be judged end to end there; it moves ``out_tok_s``."""
import statistics


def read(ctx):
    ttft = ctx["window_stats"]["ttft"]
    if len(ttft) < 2:
        return None
    return statistics.quantiles(ttft, n=100, method="inclusive")[94] * 1e3
