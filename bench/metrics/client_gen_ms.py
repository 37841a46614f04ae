"""Client Gen (``MultiServerPIR.submit``: key generation and enqueue),
host milliseconds per request, mean over the requests submitted in the
window. The harness times each ``submit`` call on the host clock."""


def read(run):
    t0, t1 = run["window"]
    xs = [r["t_gen"] - r["t_submit"] for r in run["requests"]
          if t0 <= r["t_submit"] < t1]
    return 1e3 * sum(xs) / len(xs) if xs else None
