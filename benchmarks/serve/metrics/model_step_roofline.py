"""Model step: the least time the window's step calls need (the larger
of their FLOPs over peak and the bytes they need over HBM bandwidth,
counted from shapes by ``servebench.costs``) over the device time of the
``jit_serve_step`` program's operations in the trace."""

from servebench.trace import device_time
from servebench.work import window_need_s

MODULE = "jit_serve_step"


def read(run):
    t = run.trace
    if not t or t.get("offset") is None:
        return None
    need = window_need_s(run)
    spent = device_time(t["ops"], t["t0"], t["t1"], module_prefix=MODULE)
    if need is None or spent <= 0:
        return None
    return 100.0 * need / spent
