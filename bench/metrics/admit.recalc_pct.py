"""Share of the window's wall time in the offline recalculation during
admission (``SimProfile.recalc_s``:
``StopAndWaitController.run_offline_recalculation`` after each admission,
less the planner's solves inside it). None where the program does not time
it."""


def read(win):
    if "recalc_s" not in win.profile:
        return None
    return 100.0 * win.profile["recalc_s"] / win.wall_s
