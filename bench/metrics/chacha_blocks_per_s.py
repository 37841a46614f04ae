"""ChaCha block evaluations per second of serve-step device time: the
blocks the GGM algorithm needs (``bench/work.py``) for each serve step in
the trace, at the bucket that step ran, over the device time of those
programs. An achieved rate: the VPU has no published peak."""


def read(run):
    tr, buckets = run.get("trace"), run.get("serve_buckets")
    if tr is None or not buckets or tr.serve_s <= 0:
        return None
    cfg = run["config"]
    blocks = sum(run["work"].chacha_blocks(cfg["share_kind"], b,
                                           int(cfg["n_items"]))
                 for b in buckets)
    return blocks / tr.serve_s
