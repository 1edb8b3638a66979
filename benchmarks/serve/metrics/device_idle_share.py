"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device-operation intervals / window)."""


def read(run):
    t = run.trace
    if not t or t.get("offset") is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["devices"] / t["window_s"])
