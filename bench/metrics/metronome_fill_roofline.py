"""Share of the HBM roofline that the rate solve's fill reaches.

The fill computes no matrix product, so its roofline is bound by bytes:
the least time is the bytes the real problems need (each problem's
demands, routes, capacities and rates, unpadded, 4 bytes each; counted by
``probes.fill_bytes``) over the chip's peak HBM bandwidth.  The time is the
device time of every op of the fill's programs, whichever implements the
fill.  None where the window made no fill or the trace shows none."""

PROGRAMS = ("jit_metronome_fill", "jit_progressive_fill_ref")


def read(win):
    if win.trace is None or win.peaks is None or not win.fill_bytes:
        return None
    seconds = sum(win.trace.program_s.get(p, 0.0) for p in PROGRAMS)
    if seconds <= 0.0:
        return None
    return 100.0 * win.fill_bytes / win.peaks["hbm_bytes_per_s"] / seconds
