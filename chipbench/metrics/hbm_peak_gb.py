"""hbm_peak_gb: peak bytes in use on the fullest chip after the window
(memory_stats), in GB."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
