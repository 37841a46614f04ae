"""ChaCha block evaluations per second of one chip's serve-step device
time, on a cell whose database is row-sharded over the chips: the
blocks of a full-domain GGM evaluation over one chip's shard of
``n_items // chips`` rows (``bench/work.py``), for each serve step in
the trace at the bucket it ran, over chip 0's device time of those
programs. The descent from the root to the shard's subtree (2 blocks
per query on four chips) is left out. An achieved rate per chip: the
VPU has no published peak. ``chips`` is the traced run's."""


def read(run):
    tr, buckets = run.get("trace"), run.get("serve_buckets")
    if tr is None or not buckets or tr.serve_s <= 0:
        return None
    cfg = run["config"]
    rows = int(cfg["n_items"]) // tr.chips
    blocks = sum(run["work"].chacha_blocks(cfg["share_kind"], b, rows)
                 for b in buckets)
    return blocks / tr.serve_s
