"""Share of the window's wall time in the event loop's rate-solve phase
(``SimProfile.assign_s``; it includes the wait on the device)."""


def read(win):
    return 100.0 * win.profile["assign_s"] / win.wall_s
