"""Share of the window's wall time in the realign loop during admission
(``SimProfile.realign_s``: the loop at the end of
``ClusterSimulator._schedule`` that realigns the low-priority jobs already
running). None where the program does not time it."""


def read(win):
    if "realign_s" not in win.profile:
        return None
    return 100.0 * win.profile["realign_s"] / win.wall_s
