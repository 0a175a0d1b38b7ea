"""Share of the window's rate solves answered by the simulator's whole-tick
rate memo (``SimProfile.rate_memo_hits`` over hits plus misses).  None
where the program does not count them or solved nothing."""


def read(win):
    p = win.profile
    n = p.get("rate_memo_hits", 0) + p.get("rate_memo_misses", 0)
    if n == 0:
        return None
    return 100.0 * p["rate_memo_hits"] / n
