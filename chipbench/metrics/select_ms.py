"""select_ms: mean host time of a strategy.select call in the window,
counting calls that admit nobody."""


def read(run):
    calls = run.spans.get("select")
    return 1e3 * sum(calls) / len(calls) if calls else None
