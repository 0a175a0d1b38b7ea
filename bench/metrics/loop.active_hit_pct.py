"""Share of the window's changes of the active flow set that the
simulator's membership cache answered (``SimProfile.active_hits`` over
hits plus ``active_misses``, the rebuilds of the ordered active set).
None where the program does not count them or the set never changed."""


def read(win):
    p = win.profile
    n = p.get("active_hits", 0) + p.get("active_misses", 0)
    if n == 0:
        return None
    return 100.0 * p["active_hits"] / n
