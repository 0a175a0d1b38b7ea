"""Share of the window's ticks with active flows whose next-event and
advance took the event loop's scalar pass (``SimProfile.scalar_ticks``
over ``flow_ticks``).  None where the program does not count them or no
tick had active flows."""


def read(win):
    p = win.profile
    n = p.get("flow_ticks", 0)
    if n == 0:
        return None
    return 100.0 * p.get("scalar_ticks", 0) / n
