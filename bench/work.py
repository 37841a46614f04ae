"""The work one serve step must do, from the algorithm's shapes alone.

These count what two-server DPF PIR needs, whatever kernel path or plan
implements it, so a later change to the implementation cannot change the
yardstick:

* bytes: each party-step reads the whole database view it contracts,
  ``n_items * item_bytes`` (the u32 words view for XOR shares, the int8
  byte view for additive shares; both hold one byte per database byte).
* int8 ops: the additive body is a ``[Q, N] x [N, item_bytes]`` int8
  product, ``2 * Q * N * item_bytes`` operations (multiply and add).
  The XOR body has no MXU work; its fold is counted by its bytes.
* ChaCha blocks: a full-domain GGM evaluation expands every one of the
  tree's ``N - 1`` internal nodes with one ChaCha block (both children's
  seeds and control bits come from one 512-bit block); the additive
  scheme converts every one of the ``N`` leaves with one more block (a
  Z_256 share fits the first word). Pad slots of a bucket are expanded
  too, so a step is counted at the bucket size it ran.
"""
from __future__ import annotations


def db_bytes(n_items: int, item_bytes: int) -> int:
    """Database bytes one party-step reads."""
    return n_items * item_bytes


def int8_ops(share_kind: str, bucket: int, n_items: int,
             item_bytes: int) -> int:
    """int8 multiply-add operations of one party-step (0 for XOR)."""
    if share_kind == "additive":
        return 2 * bucket * n_items * item_bytes
    return 0


def chacha_blocks(share_kind: str, bucket: int, n_items: int) -> int:
    """ChaCha block evaluations of one party-step at ``bucket`` queries."""
    per_query = n_items - 1
    if share_kind == "additive":
        per_query += n_items
    return bucket * per_query


def least_seconds(share_kind: str, bucket: int, n_items: int,
                  item_bytes: int, hbm_bytes_per_s: float,
                  int8_ops_per_s: float) -> tuple:
    """(least time of one party-step, the bound that sets it)."""
    t_bytes = db_bytes(n_items, item_bytes) / hbm_bytes_per_s
    t_ops = int8_ops(share_kind, bucket, n_items, item_bytes) / int8_ops_per_s
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "int8")
