"""Share of the window's wall time in the rate solve's memo: content keys,
lookups and stores (``FluidStats.key_s``).  None where the program does
not time it."""


def read(win):
    if "key_s" not in win.memo:
        return None
    return 100.0 * win.memo["key_s"] / win.wall_s
