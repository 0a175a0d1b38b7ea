"""Share of the window's component solves answered by the fluid engine's
memo (``FluidStats`` hits over hits plus misses)."""


def read(win):
    n = win.memo["hits"] + win.memo["misses"]
    if n == 0:
        return None
    return 100.0 * win.memo["hits"] / n
