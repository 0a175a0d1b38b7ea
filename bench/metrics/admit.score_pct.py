"""Share of the window's wall time in Score during admission
(``SimProfile.score_s``: ``score_nodes`` and ``normalize_scores`` of the
scheduler plugin, less the rotation planner's solves inside them). None
where the program does not time it."""


def read(win):
    if "score_s" not in win.profile:
        return None
    return 100.0 * win.profile["score_s"] / win.wall_s
