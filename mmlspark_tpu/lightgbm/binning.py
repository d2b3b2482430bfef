"""Quantile feature binning — LightGBM's BinMapper equivalent.

Reference: LightGBM C++ bins features into <=255 histogram bins before
training (consumed via ``LGBM_DatasetCreateFromMat/CSR``,
``DatasetAggregator.scala:335,:442``).  Here binning is split: edge *finding*
on host (numpy quantiles over a row sample — one pass, driver side), bin
*application* on device (``ops.histogram.bin_matrix`` — a vectorized
searchsorted that XLA fuses with the ingest transfer).

NaN handling: NaN sorts to bin 0 (routes left), matching the booster's
missing-goes-left convention.
"""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np


class StreamingQuantileSketch:
    """Bounded-memory quantile sketch for out-of-core edge finding: a
    vectorized row reservoir (Algorithm R) fed tile by tile.

    ``BinMapper.fit`` already computes edges from a <=``sample_cnt`` row
    sample; this sketch produces the SAME kind of sample without ever
    holding the full matrix — ``fit_streaming`` over host tiles is the
    out-of-core twin of ``fit``.  When the total row count fits the
    reservoir the sample is the exact dataset (every row retained in
    order), so streamed edges are IDENTICAL to the in-memory fit's; above
    the cap each row survives with probability ``cap / n`` (within-chunk
    replacement collisions resolve last-write-wins — a sketch, not a
    permutation-exact reservoir, which edge quantiles do not need).
    """

    def __init__(self, num_features: int, sample_cnt: int = 200_000,
                 seed: int = 3):
        self.cap = int(sample_cnt)
        self.seen = 0
        self._buf = np.empty((self.cap, num_features), np.float32)
        self._rng = np.random.default_rng(seed)

    def add(self, chunk: np.ndarray) -> "StreamingQuantileSketch":
        chunk = np.asarray(chunk, np.float32)
        m = chunk.shape[0]
        fill = max(0, min(self.cap - self.seen, m))
        if fill:
            self._buf[self.seen:self.seen + fill] = chunk[:fill]
        rest = chunk[fill:]
        if rest.shape[0]:
            s = self.seen + fill + np.arange(rest.shape[0])
            accept = self._rng.random(rest.shape[0]) < self.cap / (s + 1.0)
            idx = np.flatnonzero(accept)
            if idx.size:
                slots = self._rng.integers(0, self.cap, size=idx.size)
                self._buf[slots] = rest[idx]
        self.seen += m
        return self

    def sample(self) -> np.ndarray:
        """The retained row sample (the whole stream when it fit)."""
        return self._buf[: min(self.seen, self.cap)]


class BinMapper:
    """Per-feature quantile bin edges.  edges[f] has length (max_bin - 1),
    padded with +inf for features with fewer distinct values."""

    def __init__(self, max_bin: int = 255, categorical_features=None):
        if not 2 <= max_bin <= 256:
            raise ValueError("max_bin must be in [2, 256]")
        self.max_bin = max_bin
        self.edges: Optional[np.ndarray] = None  # (F, max_bin - 1) float32
        # categorical features bin by CATEGORY CODE (bin = clip(round(x),
        # 0, max_bin-1)); no quantile edges exist for them (reference
        # categorical handling, LightGBMBase.getCategoricalIndexes:168)
        self.categorical_features = sorted(int(i) for i in
                                           (categorical_features or []))

    @property
    def num_bins(self) -> int:
        return self.max_bin

    def fit(self, X: np.ndarray, sample_cnt: int = 200_000, seed: int = 3) -> "BinMapper":
        X = np.asarray(X, np.float32)
        n, F = X.shape
        if n > sample_cnt:
            idx = np.random.default_rng(seed).choice(n, sample_cnt, replace=False)
            X = X[idx]
        B = self.max_bin
        # threaded C++ edge finding when the data plane is available AND
        # there are cores to thread over — the reference keeps this loop
        # native too (LightGBM BinMapper); single-core, vectorized numpy
        # quantiles win over the scalar C++ sort loop
        import multiprocessing
        if X.shape[0] * F >= 1 << 16 and multiprocessing.cpu_count() >= 4:
            from ..utils.native_loader import bin_edges_native
            native = bin_edges_native(X, B)
            if native is not None:
                if self.categorical_features:  # code-binned: no edges
                    native[self.categorical_features] = np.inf
                self.edges = native
                return self
        edges = np.full((F, B - 1), np.inf, np.float32)
        qs = np.linspace(0, 1, B + 1)[1:-1]  # B-1 interior quantiles
        cats = set(self.categorical_features)
        for f in range(F):
            if f in cats:
                continue  # code-binned: no numerical edges
            col = X[:, f]
            col = col[~np.isnan(col)]
            if col.size == 0:
                continue
            uniq = np.unique(col)
            if uniq.size <= 1:
                continue
            if uniq.size <= B:
                # few distinct values: midpoints between consecutive uniques
                mids = (uniq[:-1] + uniq[1:]) / 2.0
                edges[f, :mids.size] = mids
            else:
                e = np.quantile(col, qs)
                e = np.unique(e.astype(np.float32))
                edges[f, :e.size] = e
        self.edges = edges
        return self

    def fit_streaming(self, chunks: Iterable[np.ndarray],
                      sample_cnt: int = 200_000, seed: int = 3) -> "BinMapper":
        """Out-of-core ``fit``: edges from a :class:`StreamingQuantileSketch`
        fed one host tile at a time — no full-matrix materialization.  When
        the stream's total rows fit ``sample_cnt`` the resulting edges are
        bit-identical to ``fit`` on the concatenated matrix (the reservoir
        holds every row; ``fit`` would have used them all too)."""
        sketch: Optional[StreamingQuantileSketch] = None
        for chunk in chunks:
            chunk = np.asarray(chunk, np.float32)
            if sketch is None:
                sketch = StreamingQuantileSketch(chunk.shape[1], sample_cnt,
                                                 seed)
            sketch.add(chunk)
        if sketch is None:
            raise ValueError("fit_streaming received an empty chunk stream")
        # the sample already fits fit()'s budget: no re-subsampling happens
        return self.fit(sketch.sample(), sample_cnt=sample_cnt, seed=seed)

    def transform(self, X: np.ndarray, device: bool = False) -> np.ndarray:
        """(n, F) raw -> (n, F) uint8 bins.  bin = #edges < x; NaN -> 0.

        Default is HOST binning: the uint8 result is 4x smaller than the
        float32 input, so binning before the host->device transfer quarters
        the interconnect traffic.
        Threaded C++ when the data plane + cores exist, vectorized numpy
        per-column searchsorted otherwise; ``device=True`` digitizes on the
        accelerator for data already device-resident.
        """
        if self.edges is None:
            raise RuntimeError("BinMapper not fitted")
        X = np.asarray(X, np.float32)
        if device:
            import jax.numpy as jnp
            from ..ops.histogram import bin_matrix  # module-level jit cache
            out = np.asarray(bin_matrix(jnp.asarray(X),
                                        jnp.asarray(self.edges),
                                        self.max_bin))
            return self._overwrite_cat_bins(X, out)
        import multiprocessing
        if X.size >= 1 << 16 and multiprocessing.cpu_count() >= 4:
            from ..utils.native_loader import bin_apply_native
            native = bin_apply_native(X, self.edges, self.max_bin)
            if native is not None:
                return self._overwrite_cat_bins(X, native)
        out = np.empty(X.shape, np.uint8)
        cats = set(self.categorical_features)
        for f in range(X.shape[1]):
            if f in cats:
                continue  # filled by _overwrite_cat_bins (single code path)
            finite_edges = self.edges[f][np.isfinite(self.edges[f])]
            out[:, f] = np.searchsorted(finite_edges, np.nan_to_num(X[:, f], nan=-np.inf),
                                        side="left")
        return self._overwrite_cat_bins(X, out)

    def _overwrite_cat_bins(self, X: np.ndarray, out: np.ndarray) -> np.ndarray:
        """The ONE categorical code-binning path (all transform variants end
        here): NaN -> reserved last bin; codes must be non-negative ints
        (clip+round would otherwise silently alias negatives onto code 0
        while predict-time walks compare the raw value)."""
        for f in self.categorical_features:
            col = X[:, f]
            finite = col[~np.isnan(col)]
            if finite.size and finite.min() < 0:
                raise ValueError(
                    f"categorical feature {f} holds negative codes "
                    f"(min {finite.min()}); encode categories as "
                    f"non-negative integers (e.g. via ValueIndexer)")
            codes = np.nan_to_num(col, nan=float(self.max_bin - 1))
            out[:, f] = np.clip(np.round(codes), 0, self.max_bin - 1) \
                .astype(np.uint8)
        return out

    def bin_upper_value(self) -> np.ndarray:
        """(F, max_bin-1) raw threshold value for 'bin <= t' splits (+inf pad
        means the split cannot occur there)."""
        return self.edges
