"""client_step_ms: device time of one execution of the jitted client step
(fl_train's ``train_fn``), from the trace's program events."""

PROGRAM = "jit_train_fn"


def read(run):
    t = run.trace
    if not t:
        return None
    hits = [(c, s) for name, (c, s) in t["modules"].items()
            if name.startswith(PROGRAM)]
    count = sum(c for c, _ in hits)
    return 1e3 * sum(s for _, s in hits) / count if count else None
