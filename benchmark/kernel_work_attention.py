"""The least work the attention kernels' algorithm needs, from the
conf's shapes (beside `kernel_work.py`, which counts bytes: these
kernels are bound by compute)."""

from __future__ import annotations

FORWARD_PRODUCTS = 2      # q k^T, p v
BACKWARD_PRODUCTS = 5     # q k^T again, do v^T, p^T do, ds k, ds^T q


def seen_pairs(t: int, window: int) -> int:
    """(query, key) pairs of T positions that are causal and, with a
    window, inside it: a query sees the `window` positions up to its
    own (0: all of them)."""
    if not window or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def causal_attention_flop(rows: int, t: int, heads: int, head_dim: int,
                          window: int = 0) -> int:
    """FLOP one training step's attention core requires: every product
    is `heads x head_dim` multiply-adds a pair the query sees, 2 FLOP
    each, two products forward and five backward. A forward run again
    under a checkpoint, the score tile the two backward kernels each
    compute for themselves and the masked half of a diagonal tile are
    not required work."""
    return (2 * (FORWARD_PRODUCTS + BACKWARD_PRODUCTS) * rows
            * seen_pairs(t, window) * heads * head_dim)
