"""Share of the window's wall time in admission (``SimProfile.admit_s``:
the body of ``ClusterSimulator._try_schedule``, for arrivals in the events
phase and for pending-queue retries in the step phase).  None where the
program does not time it."""


def read(win):
    if "admit_s" not in win.profile:
        return None
    return 100.0 * win.profile["admit_s"] / win.wall_s
