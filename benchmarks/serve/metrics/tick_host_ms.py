"""Serve loop: milliseconds of a window tick that are neither kernel
builds nor waits on the device, from the program's own spans: each
``serve.tick`` span's duration less ``tick_build_ms`` and
``tick_wait_ms``, averaged over the program ticks that start and end
inside the window.  The three add up to the mean tick."""

from servebench.spans import tick_means_ms


def read(run):
    means = tick_means_ms(run)
    return None if means is None else means["host"]
