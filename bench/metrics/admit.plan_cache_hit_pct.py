"""Share of the window's lookups of the rotation planner's memo
(``rotation.PlanCache``, the scheduler plugin's and the stop-and-wait
controller's) during admission that it answered
(``SimProfile.plan_cache_hits`` over hits plus ``plan_cache_misses``).
None where the program does not count them or looked nothing up."""


def read(win):
    p = win.profile
    n = p.get("plan_cache_hits", 0) + p.get("plan_cache_misses", 0)
    if n == 0:
        return None
    return 100.0 * p["plan_cache_hits"] / n
