"""Share of one chip's roofline of the jitted serve-step programs
(``jit_serve``) on a cell whose database is row-sharded over the chips,
in %: the least time one chip's share of each party-step needs, a shard
of ``n_items // chips`` rows (``bench/work.py``, at the bucket that step
ran), over chip 0's device time of those programs. Every chip scans a
shard of the same size at the same time, so chip 0 stands for each; the
whole database's work over one chip's time would read ``chips`` times
too high. ``chips`` is the traced run's."""


def read(run):
    tr, buckets = run.get("trace"), run.get("serve_buckets")
    if tr is None or not buckets or tr.serve_s <= 0:
        return None
    work, cfg, pk = run["work"], run["config"], run["peaks"]
    rows = int(cfg["n_items"]) // tr.chips
    least = sum(work.least_seconds(
        cfg["share_kind"], b, rows, int(cfg["item_bytes"]),
        pk.hbm_bytes_per_s, pk.int8_ops_per_s)[0] for b in buckets)
    return 100.0 * least / tr.serve_s
