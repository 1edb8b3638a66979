"""Model step: milliseconds a window tick spends with the host blocked on
the device, from the program's own spans: the time in ``serve.step.wait``
and ``serve.gather.wait`` under each ``serve.tick`` span, averaged over
the program ticks that start and end inside the window."""

from servebench.spans import tick_means_ms


def read(run):
    means = tick_means_ms(run)
    return None if means is None else means["wait"]
