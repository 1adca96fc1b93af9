"""setup_s: process start to window start: building, data and weights,
compiling or loading programs, and the warm-up rounds."""


def read(run):
    return run.setup_s
