"""Share of the untraced window in which the device was idle: one less the
device time per round, read from the traced rounds (the profiler slows the
host, not the device), times the window's rounds, over the window's
seconds. Averaged over the chips."""


def read(ctx):
    trace = ctx["trace"]
    rounds = trace.rounds()
    if not trace.devices or not rounds or not ctx["rounds"]:
        return None
    busy_s = trace.busy_ns() / len(rounds) * 1e-9 * ctx["rounds"]
    return 100.0 * (1.0 - busy_s / ctx["window_s"])
