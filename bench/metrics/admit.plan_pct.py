"""Share of the window's wall time in the rotation planner during admission
(``SimProfile.plan_s``: the Score phase's ``rotation.plan``,
``solve_link_batch`` and ``joint_solve_batch`` and the exhaustive or
descent searches under them, and the stop-and-wait controller's circles at
Reserve, eviction and recalculation). None where the program does not time
it."""


def read(win):
    if "plan_s" not in win.profile:
        return None
    return 100.0 * win.profile["plan_s"] / win.wall_s
