"""Share of the window's wall time in the event loop's advance phase
(``SimProfile.advance_s``: flow volumes and delivered bytes moved on to the
next event)."""


def read(win):
    return 100.0 * win.profile["advance_s"] / win.wall_s
