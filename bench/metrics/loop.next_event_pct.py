"""Share of the window's wall time in the event loop's next-event phase
(``SimProfile.next_event_s``: the next timed job event, flow finish,
dynamic event or arrival)."""


def read(win):
    return 100.0 * win.profile["next_event_s"] / win.wall_s
