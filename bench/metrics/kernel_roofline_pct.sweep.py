"""Kernel time against its bandwidth roofline, in a kernel sweep cell.

The least time is the algorithmic bytes of every member-slot the traced
calls ran (``slot_bytes`` of the policy's reference, from the cell's
shapes alone) over the chip's HBM bandwidth; the time is the device time
of the kernel's events in the traced part of the window.  None where the
trace shows no kernel event."""


def read(run):
    names = run.cell.get("kernel_names")
    if run.reduction is None or not names:
        return None
    kernel_s = run.reduction.kernel_s(names)
    if kernel_s <= 0:
        return None
    need = run.reference.slot_bytes(run.config["sizes"]) \
        * run.work["traced_member_slots"]
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / kernel_s
