"""Share of the roofline of the jitted serve-step programs (``jit_serve``),
in %: the least time the algorithm's work needs on this chip over the
device time of those programs in the trace. Least time per party-step is
the larger of the database view's bytes over the HBM peak and, for
additive shares, the int8 ops over the int8 peak (``bench/work.py``),
counted at the bucket each traced step ran."""


def read(run):
    tr, buckets = run.get("trace"), run.get("serve_buckets")
    if tr is None or not buckets or tr.serve_s <= 0:
        return None
    work, cfg, pk = run["work"], run["config"], run["peaks"]
    least = sum(work.least_seconds(
        cfg["share_kind"], b, int(cfg["n_items"]), int(cfg["item_bytes"]),
        pk.hbm_bytes_per_s, pk.int8_ops_per_s)[0] for b in buckets)
    return 100.0 * least / tr.serve_s
