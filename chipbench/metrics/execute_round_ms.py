"""execute_round_ms: mean host time per round in the window outside the
layers' spans (selection, local training, aggregation and evaluation):
``execute_round``'s power-sharing step loop and the round loop's own host
work."""


def read(run):
    calls = run.spans.get("execute_round")
    return 1e3 * sum(calls) / len(calls) if calls else None
