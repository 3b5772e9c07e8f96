"""Bytes that the benchmark's work needs, from its own sizes."""

from __future__ import annotations


def copy_bytes(bytes_moved: int) -> int:
    """HBM traffic of copying ``bytes_moved`` bytes of blocks: read and write."""
    return 2 * int(bytes_moved)
