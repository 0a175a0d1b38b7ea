"""Share of the window's wall time in Filter during admission
(``SimProfile.filter_s``: the Filter loop of
``SchedulingFramework.schedule_pod``: the spread cap, Eq. 13 and Eq. 14 on
every node). None where the program does not time it."""


def read(win):
    if "filter_s" not in win.profile:
        return None
    return 100.0 * win.profile["filter_s"] / win.wall_s
