"""Share of the traced window in which no op ran on the device: 1 minus
the union of device-op intervals over the window."""


def read(win):
    if win.trace is None or win.trace.window_s <= 0:
        return None
    return 100.0 * win.trace.idle_share()
