"""Share of the window's wall time spent building the dirty components'
fill problems before the solve (``SimProfile.problems_s``: dirty test,
paths, capacities).  None where the program does not time it."""


def read(win):
    if "problems_s" not in win.profile:
        return None
    return 100.0 * win.profile["problems_s"] / win.wall_s
