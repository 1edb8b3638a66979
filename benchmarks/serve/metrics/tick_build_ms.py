"""Banked kernels: milliseconds a window tick spends building kernels,
from the program's own spans: ``build_s`` (JAX's trace, lower and
compile phases) summed over each ``serve.tick`` span and every span
under it, averaged over the program ticks that start and end inside the
window."""

from servebench.spans import tick_means_ms


def read(run):
    means = tick_means_ms(run)
    return None if means is None else means["build"]
