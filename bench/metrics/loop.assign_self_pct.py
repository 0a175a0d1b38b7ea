"""Share of the window's wall time in the assign phase outside its timed
parts: ``assign_s`` less ``components_s`` and ``problems_s``
(``SimProfile``) and ``batch_s`` (``FluidStats``, the body of
``solve_batch``).  It holds the phase's bookkeeping and whatever is wrapped
around ``solve_batch`` from outside.  None where the program does not time
the parts."""


def read(win):
    p, m = win.profile, win.memo
    if "components_s" not in p or "batch_s" not in m:
        return None
    own = p["assign_s"] - p["components_s"] - p["problems_s"] - m["batch_s"]
    return 100.0 * own / win.wall_s
