"""Share of the window's wall time spent finding the active flows'
affinity components (``SimProfile.components_s``, inside assign).  None
where the program does not time it."""


def read(win):
    if "components_s" not in win.profile:
        return None
    return 100.0 * win.profile["components_s"] / win.wall_s
