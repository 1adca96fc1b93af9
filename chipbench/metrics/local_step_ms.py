"""local_step_ms: host time of JaxTrainer.local_update over its local
steps, averaged over the window's updates. An update ends in a host sync,
so its time is complete."""


def read(run):
    per_step = [s / n for s, n in run.updates if n]
    return 1e3 * sum(per_step) / len(per_step) if per_step else None
