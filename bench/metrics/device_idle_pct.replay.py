"""Share of the traced window in which the device ran no operation, in
the replay cells (profiler trace)."""


def read(run):
    return None if run.reduction is None else run.reduction.idle_pct
