"""round_s: window seconds over the rounds completed in it."""


def read(run):
    return run.window_s / len(run.round_s)
