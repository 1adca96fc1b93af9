"""aggregate_eval_ms: host time of aggregate and evaluate per round in the
window; evaluate ends in a sync, so it waits for aggregate's device work."""


def read(run):
    calls = run.spans.get("aggregate_eval")
    return 1e3 * sum(calls) / len(run.round_s) if calls else None
