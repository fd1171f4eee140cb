"""runtime_self_pct: share of the window in which the host is in none of
the benchmark's layer spans (client step, wire stack, hub): the FL
runtime's own work (server and scheduler loops, the network simulator)."""


def read(run):
    if run.window_s <= 0:
        return None
    covered = run.span_time({"client_step", "wire", "hub"})
    return 100.0 * (1.0 - covered / run.window_s)
