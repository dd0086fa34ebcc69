"""The least work a kernel's algorithm needs, from its shapes."""

from __future__ import annotations

from typing import Sequence


def _size(shape: Sequence[int]) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def lrn_fwd_bytes(shape: Sequence[int], itemsize: int) -> int:
    """Read x, write y."""
    return 2 * _size(shape) * itemsize


def lrn_bwd_bytes(shape: Sequence[int], itemsize: int) -> int:
    """Read x and dy, write dx."""
    return 3 * _size(shape) * itemsize
