"""Column-wise CSV text for the benchmark's dataset generators.

Each helper turns a numpy column into an ``(n, width)`` array of ASCII
bytes, NUL where a cell is shorter than its column; ``join`` lays the
columns out as comma-separated, newline-terminated lines and drops the
NULs. Numbers are written with a fixed number of decimals and no leading
zeros, so every value is what ``float()`` or ``int()`` of its text
gives, in the engine and in the reference alike.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int) -> np.random.Generator:
    """A generator for any whole-number seed, negative or past 64 bits."""
    return np.random.default_rng(seed % 2**64)


def digits(vals, width: int) -> np.ndarray:
    """Zero-padded decimal digits of non-negative ints."""
    v = np.asarray(vals, dtype=np.int64).copy()
    if (v < 0).any():
        raise ValueError("negative value")
    out = np.empty((v.shape[0], width), dtype=np.uint8)
    for j in range(width - 1, -1, -1):
        out[:, j] = 48 + v % 10
        v //= 10
    if v.any():
        raise ValueError(f"value does not fit {width} digits")
    return out


def unpadded(d: np.ndarray) -> np.ndarray:
    """Blank (NUL) the leading zeros of an integer's digits, keeping one."""
    lead = np.cumprod(d[:, :-1] == 48, axis=1).astype(bool)
    d[:, :-1][lead] = 0
    return d


def integer(vals, width: int) -> np.ndarray:
    """Non-negative ints as ``%d`` text."""
    return unpadded(digits(vals, width))


def decimal(x, int_width: int, decimals: int) -> np.ndarray:
    """Floats as ``%.<decimals>f`` text, a minus sign where negative."""
    x = np.asarray(x, dtype=np.float64)
    scaled = np.rint(np.abs(x) * 10**decimals).astype(np.int64)
    return fixed_point(np.where(x < 0, -scaled, scaled), int_width, decimals)


def fixed_point(scaled, int_width: int, decimals: int) -> np.ndarray:
    """Ints counted in units of ``10**-decimals`` (cents for 2) as
    ``%.<decimals>f`` text, a minus sign where negative."""
    scaled = np.asarray(scaled, dtype=np.int64)
    mag = np.abs(scaled)
    sign = np.where(scaled < 0, ord("-"), 0).astype(np.uint8)[:, None]
    whole = integer(mag // 10**decimals, int_width)
    dot = np.full((len(mag), 1), ord("."), dtype=np.uint8)
    return np.hstack([sign, whole, dot, digits(mag % 10**decimals, decimals)])


def const(text: str, n: int) -> np.ndarray:
    return np.tile(np.frombuffer(text.encode(), dtype=np.uint8), (n, 1))


def choice(idx, words) -> np.ndarray:
    """``words[idx]`` for each row."""
    width = max(map(len, words))
    table = np.zeros((len(words), width), dtype=np.uint8)
    for i, w in enumerate(words):
        table[i, :len(w)] = np.frombuffer(w.encode(), dtype=np.uint8)
    return table[np.asarray(idx)]


def timestamps(seconds, start: str) -> np.ndarray:
    """``YYYY-MM-DD HH:MM:SS`` of ``seconds`` after ``start``."""
    t = np.datetime64(start, "s") + np.asarray(seconds, dtype=np.int64)
    text = np.datetime_as_string(t, unit="s").astype("S19")
    out = np.frombuffer(text.tobytes(), dtype=np.uint8).reshape(-1, 19).copy()
    out[:, 10] = ord(" ")
    return out


def join(columns) -> bytes:
    """Comma-separated, newline-terminated lines of the text columns."""
    n = len(columns[0])
    blocks = []
    for i, c in enumerate(columns):
        if i:
            blocks.append(const(",", n))
        blocks.append(c)
    blocks.append(const("\n", n))
    flat = np.hstack(blocks).ravel()
    return flat[flat != 0].tobytes()
