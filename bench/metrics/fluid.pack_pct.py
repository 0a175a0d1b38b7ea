"""Share of the window's wall time in the host side of the fill: problem
matrices, sorting, dummies, padding and unpadding (``FluidStats.pack_s``).
None where the program does not time it."""


def read(win):
    if "pack_s" not in win.memo:
        return None
    return 100.0 * win.memo["pack_s"] / win.wall_s
