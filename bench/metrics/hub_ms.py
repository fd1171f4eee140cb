"""hub_ms: host time per round in the hub's own work: its wire stack
(decoding received updates, encoding the model it sends out), FedAvg or
the streaming fold, and the scheduler's merge."""


def read(run):
    n = len(run.rounds_in_window())
    if not n:
        return None
    iv = [(s, e) for c, side, s, e in run.spans
          if c == "hub" or (c == "wire" and side == "server")]
    from bench.harness import union_length
    return 1e3 * union_length(iv, *run.window) / n
