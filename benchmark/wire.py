"""What the flow modules share: fixed-width rendering of numbers into a
uint8 line matrix, and rounding to bfloat16 for the low-precision control."""

import numpy as np


def field(template: bytes, mark: bytes, skip: int = 0) -> slice:
    """Where ``mark`` (less its first ``skip`` bytes) sits in a line."""
    at = template.index(mark) + skip
    return slice(at, at + len(mark) - skip)


def digits(x: np.ndarray, width: int, pad: int) -> np.ndarray:
    """``x`` as ``width`` ASCII digits a row; leading zeros become
    ``pad`` (a space: JSON allows none before a number's first digit)."""
    x = x.astype(np.int64)
    out = np.empty((len(x), width), np.uint8)
    lead = np.ones(len(x), bool)
    for k in range(width):
        d = (x // 10 ** (width - 1 - k)) % 10
        lead &= (d == 0) & (k < width - 1)
        out[:, k] = np.where(lead, pad, d + 48)
    return out


def bfloat16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 (nearest, ties to even), as float32."""
    u = np.asarray(x, np.float32).view(np.uint32)
    u = (u + (((u >> 16) & 1) + 0x7FFF)) & 0xFFFF0000
    return u.view(np.float32)
