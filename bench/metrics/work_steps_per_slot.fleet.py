"""Work steps of the placement work list per member-slot, in the fleet
sweep cell: the program's ``steps`` summed over the window's calls, over
their member-slots.  None where the program reports no ``steps``."""


def read(run):
    steps = run.work.get("steps")
    if steps is None or not run.work["member_slots"]:
        return None
    return steps / run.work["member_slots"]
