"""Device idle share of the traced window, in %: 1 - (union of the
intervals in which a program ran on the chip) / (traced window)."""


def read(run):
    tr = run.get("trace")
    if tr is None or tr.window_s <= 0 or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
