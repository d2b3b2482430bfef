"""Inputs from ``--seed``: the same seed gives the same data, images and
arrival times.  One general generator per kind of input; the sizes come from
the configuration and traffic files.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

#: streams drawn from one ``--seed``; a new kind of input takes a new number
STREAM_FEATURES, STREAM_LABEL_NOISE, STREAM_OFFSETS, STREAM_FLIPS, \
    STREAM_IMAGES, STREAM_ARRIVALS, STREAM_PICKS, STREAM_SAMPLE = range(8)


def stream(seed: int, which: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(which)])


def tabular(seed: int, rows: int, features: int, block_rows: int,
            holdout: int, noise: float, threads: int = 8
            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """``rows`` x ``features`` float32 training rows and ``holdout`` more,
    labelled ``x0 + 0.5 x1 + noise * e > 0``.

    Only the first block of ``block_rows`` (and the held-out rows) is drawn
    from the generator; block ``k`` is that block with its columns rolled by
    ``k`` and its rows rotated by a seeded offset, written straight into
    place.  Every row of the table is then a distinct, standard-normal
    feature vector and the label is one function of a row's own features, so
    the trainer sees the statistics of an i.i.d. table at the cost of
    copying memory.  Blocks are written by a few threads: on a fresh virtual
    machine the first touch of a page is what takes the time."""
    if rows % block_rows:
        raise ValueError(f"rows {rows} is not whole blocks of {block_rows}")
    blocks = rows // block_rows
    if blocks > features:
        raise ValueError("more blocks than column rolls")
    rng = stream(seed, STREAM_FEATURES)
    X = np.empty((rows + holdout, features), np.float32)
    rng.standard_normal(out=X[:block_rows], dtype=np.float32)
    rng.standard_normal(out=X[rows:], dtype=np.float32)
    base = X[:block_rows]
    offsets = stream(seed, STREAM_OFFSETS).integers(1, block_rows, blocks)

    def fill(k: int) -> None:
        out = X[k * block_rows:(k + 1) * block_rows]
        off, f = int(offsets[k]), features
        out[:block_rows - off, k:] = base[off:, :f - k]
        out[:block_rows - off, :k] = base[off:, f - k:]
        out[block_rows - off:, k:] = base[:off, :f - k]
        out[block_rows - off:, :k] = base[:off, f - k:]

    with ThreadPoolExecutor(max(1, threads)) as pool:
        list(pool.map(fill, range(1, blocks)))
    e = stream(seed, STREAM_LABEL_NOISE).standard_normal(
        rows + holdout, dtype=np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + noise * e > 0).astype(np.float32)
    return X[:rows], y[:rows], X[rows:], y[rows:]


def flip_labels(y: np.ndarray, seed: int, fit: int, flips: int) -> np.ndarray:
    """A copy of ``y`` with ``flips`` seeded positions inverted: fit ``fit``
    of a loop trains on labels that differ from every other fit's, as the
    folds of a cross-validation or the trials of a tuner do."""
    out = y.copy()
    at = np.random.default_rng([int(seed), STREAM_FLIPS, int(fit)]).choice(
        y.shape[0], size=min(flips, y.shape[0]), replace=False)
    out[at] = 1.0 - out[at]
    return out


def images(seed: int, count: int, size: int) -> np.ndarray:
    """``count`` uint8 HWC images of ``size`` x ``size`` x 3, as an image
    reader yields them."""
    return stream(seed, STREAM_IMAGES).integers(
        0, 256, (count, size, size, 3), dtype=np.uint8)


def poisson_arrivals(seed: int, rate_per_s: float, seconds: float
                     ) -> np.ndarray:
    """Due times, in seconds from the start of the window, of a Poisson
    process of ``rate_per_s`` conditioned on its count: exactly
    ``round(rate * seconds)`` arrivals, uniform over the window and sorted.
    The amount of work is then the same for every seed."""
    count = max(1, int(round(rate_per_s * seconds)))
    return np.sort(stream(seed, STREAM_ARRIVALS).uniform(0.0, seconds, count))


def picks(seed: int, count: int, pool: int) -> np.ndarray:
    """Which image of the pool each request carries."""
    return stream(seed, STREAM_PICKS).integers(0, pool, count)
