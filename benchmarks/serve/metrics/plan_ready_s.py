"""Plan plane: seconds from the KV plan's submit to the server serving
the solved layout (host clock, during set-up)."""


def read(run):
    return run.plan_ready_s
