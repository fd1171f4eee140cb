"""round_s: window seconds over the FL rounds completed in it (sync
rounds, fedbuff merges). Host clock; every round ends in a device sync."""


def read(run):
    n = len(run.rounds_in_window())
    return run.window_s / n if n else None
