"""Banked kernels: backend compilations (JAX's
``/jax/core/compile/backend_compile_duration`` events) inside the window,
per tick started in it.  The program builds fresh resolution closures and
calls the gather and record-write kernels outside any jit, so each call
compiles again."""

from servebench.work import window_ticks


def read(run):
    ticks = window_ticks(run)
    return run.compiles_in_window / len(ticks) if ticks else None
