"""95th percentile of queue wait (enqueue -> admitted into a slot), in ms,
over the requests sent in the window. Read from the engine's ``basic``
telemetry timelines, which the traced run turns on."""
import statistics


def read(ctx):
    eng, drv = ctx["eng"], ctx["driver"]
    t0, t1 = ctx["window"]
    if not eng.tel.enabled:
        return None
    waits = []
    for rec in drv.recs.values():
        if rec.warm or not (t0 <= rec.t0 < t1):
            continue
        tl = eng.tel.timeline(rec.uid)
        if tl and "queue_s" in tl["summary"]:
            waits.append(tl["summary"]["queue_s"] * 1e3)
    if len(waits) < 2:
        return None
    return statistics.quantiles(waits, n=100, method="inclusive")[94]
