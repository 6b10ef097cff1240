"""Device time of the cell's kernel per work step, in the fleet sweep
cell: the kernel events' device time in the traced part of the window
over the work steps (``steps``) the traced calls ran, in microseconds.
None where the trace shows no kernel event or the program reports no
``steps``."""


def read(run):
    names = run.cell.get("kernel_names")
    steps = run.work.get("traced_steps")
    if run.reduction is None or not names or not steps:
        return None
    kernel_s = run.reduction.kernel_s(names)
    return 1e6 * kernel_s / steps if kernel_s > 0 else None
