"""update_p95_s: 95th percentile over the client updates completed in the
window of one silo's update time, from its receive of the global model
(decode) through its local steps to the update handed to its backend
(encode). Host clock."""
from bench.harness import percentile


def read(run):
    d = [e - s for _, s, e in run.updates_in_window()]
    return percentile(d, 95) if d else None
