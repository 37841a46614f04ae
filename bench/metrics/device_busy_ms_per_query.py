"""Device busy milliseconds per verified query: the chip's busy time in
the traced window over the queries answered inside that window."""


def read(run):
    tr = run.get("trace")
    if tr is None or tr.busy_s <= 0:
        return None
    t0, t1 = run["trace_window"]
    n = sum(1 for r in run["requests"]
            if "record" in r and t0 < r["t_done"] <= t1)
    return 1e3 * tr.busy_s / n if n else None
