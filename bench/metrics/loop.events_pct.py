"""Share of the window's wall time in the event loop's events phase
(``SimProfile.events_s``): departures and online arrivals, which is where
admission runs."""


def read(win):
    return 100.0 * win.profile["events_s"] / win.wall_s
