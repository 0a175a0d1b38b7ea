"""Share of the window's wall time in the event loop's step phase
(``SimProfile.step_s``: due jobs change phase; a job that completes frees
its pods and retries the pending queue, which is admission)."""


def read(win):
    return 100.0 * win.profile["step_s"] / win.wall_s
