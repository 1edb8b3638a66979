"""Banked kernels: the least time the window's banked gathers and record
writes need (the logical rows and elements they read and write, over HBM
bandwidth) over their device time in the trace: the Mosaic kernels
(``tpu_custom_call``) outside the model step, which are the banked
gather and record write.  The copies that pad the record table to whole
tiles around each call are not kernel time and not counted.  Each tick
gathers the last ``gather_window`` records of every active slot and
writes one record per prompt token, per first token and per decoded
token."""

from servebench.costs import gather_bytes, record_write_bytes
from servebench.trace import device_time
from servebench.work import window_ticks

KERNEL = "tpu_custom_call"
STEP = "jit_serve_step"


def read(run):
    t = run.trace
    if not t or t.get("offset") is None:
        return None
    spent = device_time(t["ops"], t["t0"], t["t1"], name_prefix=KERNEL,
                        exclude_module=STEP)
    ticks = window_ticks(run)
    admitted = sum(1 for r in run.reqs if r.first_call is not None
                   and run.first_window_call <= r.first_call
                   < run.window_calls_end)
    nbytes = sum(gather_bytes(tk.tokens * run.gather_window, run.spec.slots)
                 + record_write_bytes(tk.prefill_tokens + tk.tokens)
                 for tk in ticks) + record_write_bytes(admitted)
    if spent <= 0:
        return None
    return 100.0 * nbytes / run.peaks.hbm_bytes_per_s / spent
