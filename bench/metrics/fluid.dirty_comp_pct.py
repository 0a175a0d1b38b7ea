"""Share of the affinity components found in the window that were dirty
and went to the solve (``SimProfile.dirty_components`` over
``components``): the part of the components rebuilt every tick that was
used.  None where the program does not count them or found none."""


def read(win):
    if not win.profile.get("components"):
        return None
    return 100.0 * win.profile["dirty_components"] / win.profile["components"]
