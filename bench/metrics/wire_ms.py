"""wire_ms: client-side host time in the wire stack (Channel encode and
decode, encode_many) per client update completed in the window."""


def read(run):
    n = len(run.updates_in_window())
    if not n:
        return None
    return 1e3 * run.span_time({"wire"}, lambda side: side != "server") / n
