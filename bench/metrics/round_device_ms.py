"""Device time per round: the union of the device's busy intervals in the
traced window, averaged over the chips, over the rounds traced.
Independent of the programs' names."""


def read(ctx):
    rounds = ctx["trace"].rounds()
    if not rounds:
        return None
    return ctx["trace"].busy_ns() / len(rounds) * 1e-6
