"""Simulated seconds advanced per wall second, over the whole window:
every chunk's simulated time over every chunk's wall time."""


def read(win):
    return win.sim_s / win.wall_s
