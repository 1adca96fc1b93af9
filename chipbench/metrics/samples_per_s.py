"""samples_per_s: rows trained by local steps in the window (a sequence for
a language model) over the window seconds."""


def read(run):
    return run.rows / run.window_s
