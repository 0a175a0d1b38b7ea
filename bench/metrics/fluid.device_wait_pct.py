"""Share of the window's wall time from the call into the fill until its
rates are on the host (``FluidStats.device_s``): the one interval in which
the device works for the loop.  None where the program does not time it."""


def read(win):
    if "device_s" not in win.memo:
        return None
    return 100.0 * win.memo["device_s"] / win.wall_s
