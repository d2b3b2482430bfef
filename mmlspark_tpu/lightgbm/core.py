"""GBDT training core — leaf-wise and level-wise tree growth as jitted XLA.

Reference hot path: ``TrainUtils.trainCore`` (``TrainUtils.scala:92-159``)
calls ``LGBM_BoosterUpdateOneIter`` per iteration — native histogram build +
socket allreduce + split finding.  TPU-native, one boosting iteration is a
single jitted function built from:

  histograms  = one fused segment-sum scatter   (ops.histogram)       [VPU]
  split find  = cumsum + argmax over (node, feature, bin)             [VPU]
  routing     = gather of each row's split decision                   [VPU]

Two growth strategies share those kernels:

- **leaf-wise** (LightGBM's defining best-first growth, the default when
  ``num_leaves`` is set): a ``lax.scan`` over ``num_leaves - 1`` split
  steps; each step splits the leaf with the global best gain, builds the
  left child's histogram with one masked pass and derives the right
  sibling by subtraction.  Trees are arrays-of-nodes with explicit child
  pointers (non-perfect shapes, ``num_leaves`` honoured exactly).
- **level-wise** (``max_depth``-driven, XGBoost-style depth growth): the
  python loop over static depth unrolls into XLA, one histogram pass per
  level for all frontier nodes at once — fewer data passes per tree, the
  fastest mode for the throughput bench.

Across data shards the histogram tensors are psum'd over the mesh's ``data``
axis — this replaces LightGBM's ``data_parallel`` TCP-ring allreduce.
``voting_parallel`` (reference ``parallelism`` + ``topK``,
``TrainParams.scala:11-12``) is implemented for real in both growth modes:
shards vote their local top-k features per node and only the global top-2k
features' histograms cross the mesh, cutting per-node ICI traffic from
O(F*B) to O(k*B).

Supports the reference's boosting modes (``boosting_type`` gbdt/rf/dart/goss,
``params/TrainParams.scala``), objectives, bagging, feature_fraction, L1/L2,
min_data_in_leaf, early stopping, and warm start from an existing booster.
"""
from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..models.gbdt import GBDTBooster
from ..observability.compute import instrumented_jit
from ..ops.histogram import build_histograms, xla_backend
from ..utils.device import platform
from .binning import BinMapper

#: The device phases of one boosting iteration: the ``jax.named_scope`` names
#: the grower programs carry, written here and nowhere else.  A scope is
#: metadata on the lowered operations (it adds none and changes no fusion);
#: a profiler trace shows it in each operation's path, inside an ``L<d>``
#: scope per tree level, and ``benchmark/phase_times.py`` reads device
#: seconds per phase from that (the ``gbdt.*_ms_per_iter`` metrics).
DEVICE_PHASES = ("gbdt.grad", "gbdt.quantize", "gbdt.layout", "gbdt.hist",
                 "gbdt.allreduce", "gbdt.split", "gbdt.route", "gbdt.update")
(PHASE_GRAD, PHASE_QUANTIZE, PHASE_LAYOUT, PHASE_HIST, PHASE_ALLREDUCE,
 PHASE_SPLIT, PHASE_ROUTE, PHASE_UPDATE) = DEVICE_PHASES


@dataclasses.dataclass
class GBDTParams:
    num_iterations: int = 100
    learning_rate: float = 0.1
    max_depth: int = 0               # leaf-wise: depth cap (0 = uncapped);
    #                                  level-wise: tree depth (0 -> 5)
    num_leaves: Optional[int] = None  # leaf-wise leaf budget (LightGBM
    #                                  numLeaves, default 31 when leaf growth)
    growth: str = "auto"             # leaf | level | auto (leaf iff
    #                                  num_leaves given, else level)
    max_bin: int = 255
    objective: str = "binary"
    num_class: int = 1
    boosting_type: str = "gbdt"      # gbdt | rf | dart | goss
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    feature_fraction: float = 1.0
    # goss
    top_rate: float = 0.2
    other_rate: float = 0.1
    # dart
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    # misc
    max_delta_step: float = 0.0
    sigmoid: float = 1.0
    alpha: float = 0.9               # huber / quantile
    tweedie_variance_power: float = 1.5  # tweedie: 1 (poisson) .. 2 (gamma)
    early_stopping_round: int = 0
    metric: str = ""
    seed: int = 0
    verbosity: int = -1
    # categorical splits (reference getCategoricalIndexes,
    # LightGBMBase.scala:168): these feature indices bin by CATEGORY CODE.
    # Low-cardinality features (<= max_cat_to_onehot observed codes) split
    # one-vs-rest (code == c); higher-cardinality features use LightGBM's
    # sorted-subset (many-vs-many) search: codes sorted by grad/hess ratio,
    # prefix subsets scanned from the same histogram tensor
    categorical_features: Optional[Tuple[int, ...]] = None
    max_cat_to_onehot: int = 4
    cat_smooth: float = 10.0         # ratio denominator smoothing
    cat_l2: float = 10.0             # extra L2 when scoring subset splits
    max_cat_threshold: int = 32      # cap on the smaller side's category count
    # resolved in train() from observed cardinalities (data-dependent, part
    # of the jit cache key); settable explicitly for tests
    cat_subset: Optional[Tuple[int, ...]] = None
    # voting-parallel (reference parallelism=voting_parallel + topK,
    # TrainParams.scala:11-12): each shard votes its local top-k features
    # per node; only the global top-2k features' histograms are allreduced,
    # cutting ICI traffic from O(F*B) to O(k*B) per node on wide data.
    # 0 = full histogram psum (data_parallel).
    voting_k: int = 0
    # quantized training (LightGBM 4.x "Quantized Training of GBDT", same
    # param names): per-row grad/hess stochastically rounded to
    # num_grad_quant_bins integer levels once per iteration, histograms
    # accumulated as packed integers (ops.histogram quantized builders) and
    # rescaled only at split-gain time; sibling subtraction is exact in
    # integer space.  None = auto: ON for accelerator backends, OFF on CPU
    # (train() resolves it)
    use_quantized_grad: Optional[bool] = None
    num_grad_quant_bins: int = 16

    def resolve(self) -> "GBDTParams":
        """Normalize growth mode.  Leaf-wise (LightGBM semantics: numLeaves
        default 31, ``LightGBMParams.scala:331-332``) grows by global best
        gain with ``num_leaves`` as the stop and ``max_depth`` as an optional
        cap; level-wise grows a perfect depth-``max_depth`` tree."""
        p = dataclasses.replace(self)
        if p.growth == "auto":
            p.growth = "leaf" if p.num_leaves else "level"
        if p.growth == "level":
            if p.max_depth <= 0:
                p.max_depth = max(1, int(math.ceil(math.log2(max(2, p.num_leaves))))) \
                    if p.num_leaves else 5
            p.num_leaves = 2 ** p.max_depth
        elif p.growth == "leaf":
            p.num_leaves = p.num_leaves or 31
            if p.num_leaves < 2:
                raise ValueError("num_leaves must be >= 2")
        else:
            raise ValueError(f"growth must be leaf|level|auto, got {p.growth!r}")
        if p.boosting_type == "rf" and p.bagging_freq == 0:
            p.bagging_freq, p.bagging_fraction = 1, min(p.bagging_fraction, 0.632)
        if not 4 <= p.num_grad_quant_bins <= 128:
            raise ValueError("num_grad_quant_bins must be in [4, 128] "
                             f"(int8 operand lanes), got {p.num_grad_quant_bins}")
        return p

    @property
    def depth_bound(self) -> int:
        """Static walk-iteration bound for trees grown under these params
        (call on a resolved instance)."""
        if self.growth == "level":
            return max(1, self.max_depth)
        cap = self.max_depth if self.max_depth > 0 else (self.num_leaves or 31) - 1
        return max(1, min(cap, (self.num_leaves or 31) - 1))


# ---------------------------------------------------------------------------
# objectives: (scores, y, w) -> grad, hess     [all jitted, (n,K) scores]
# ---------------------------------------------------------------------------

def make_objective(params: GBDTParams) -> Callable:
    import jax.numpy as jnp
    obj, K = params.objective, params.num_class
    sig, alpha = params.sigmoid, params.alpha

    def binary(scores, y, w):
        p = 1.0 / (1.0 + jnp.exp(-sig * scores[:, 0]))
        g = sig * (p - y)
        h = jnp.maximum(sig * sig * p * (1.0 - p), 1e-16)
        return (g * w)[:, None], (h * w)[:, None]

    def multiclass(scores, y, w):
        z = scores - scores.max(axis=1, keepdims=True)
        e = jnp.exp(z)
        p = e / e.sum(axis=1, keepdims=True)
        onehot = (y[:, None] == jnp.arange(K)[None, :]).astype(p.dtype)
        g = p - onehot
        h = jnp.maximum(2.0 * p * (1.0 - p), 1e-16)
        return g * w[:, None], h * w[:, None]

    def l2(scores, y, w):
        g = scores[:, 0] - y
        return (g * w)[:, None], (w * jnp.ones_like(g))[:, None]

    def l1(scores, y, w):
        g = jnp.sign(scores[:, 0] - y)
        return (g * w)[:, None], (w * jnp.ones_like(g))[:, None]

    def huber(scores, y, w):
        d = scores[:, 0] - y
        g = jnp.clip(d, -alpha, alpha)
        return (g * w)[:, None], (w * jnp.ones_like(g))[:, None]

    def quantile(scores, y, w):
        d = scores[:, 0] - y
        g = jnp.where(d >= 0, 1.0 - alpha, -alpha)
        return (g * w)[:, None], (w * jnp.ones_like(g))[:, None]

    def poisson(scores, y, w):
        # log link: raw score s models log(mean); nll grad = exp(s) - y
        mu = jnp.exp(jnp.clip(scores[:, 0], -30.0, 30.0))
        g = mu - y
        h = jnp.maximum(mu, 1e-16)
        return (g * w)[:, None], (h * w)[:, None]

    rho = params.tweedie_variance_power

    def tweedie(scores, y, w):
        # compound-Poisson deviance with log link, variance power rho in
        # (1, 2): grad = -y*e^{(1-rho)s} + e^{(2-rho)s}
        sarr = jnp.clip(scores[:, 0], -30.0, 30.0)
        a = jnp.exp((1.0 - rho) * sarr)
        b = jnp.exp((2.0 - rho) * sarr)
        g = -y * a + b
        h = jnp.maximum(-(1.0 - rho) * y * a + (2.0 - rho) * b, 1e-16)
        return (g * w)[:, None], (h * w)[:, None]

    def gamma(scores, y, w):
        # gamma nll with log link: grad = 1 - y*e^{-s}, hess = y*e^{-s}
        e = jnp.exp(-jnp.clip(scores[:, 0], -30.0, 30.0))
        g = 1.0 - y * e
        h = jnp.maximum(y * e, 1e-16)
        return (g * w)[:, None], (h * w)[:, None]

    table = {"binary": binary, "multiclass": multiclass, "regression": l2,
             "regression_l1": l1, "huber": huber, "quantile": quantile,
             "poisson": poisson, "tweedie": tweedie, "gamma": gamma}
    if obj not in table and obj != "lambdarank":
        raise ValueError(f"unknown objective {obj!r}")
    return table.get(obj)


def make_lambdarank_grad_fn(y: np.ndarray, group_ptr: np.ndarray,
                            sigmoid: float = 1.0):
    """Device-resident LambdaRank gradients with |ΔNDCG| weighting.

    Padded-group tensorization: groups packed to (Q, Gmax) so the pairwise
    (Q, Gmax, Gmax) lambda computation is one jitted einsum-like pass —
    the XLA-friendly reshape of the reference's per-query C++ loops.

    The pack/unpack is INDEX GATHERS built once on host: the returned
    ``fn(scores_dev) -> (g, h)`` stays entirely on device, so the boosting
    loop pays zero host round trips per iteration (round-1 weak item 5:
    the old path re-packed numpy groups every iteration).
    """
    import jax
    import jax.numpy as jnp

    n = len(y)
    q = len(group_ptr) - 1
    gmax = int(max(group_ptr[i + 1] - group_ptr[i] for i in range(q)))
    pack_idx = np.zeros((q, gmax), np.int32)   # slot -> row (0 on padding)
    M_np = np.zeros((q, gmax), np.float32)
    row_q = np.zeros(n, np.int32)              # row -> (group, slot)
    row_slot = np.zeros(n, np.int32)
    covered_np = np.zeros(n, bool)             # rows outside group_ptr get 0
    for i in range(q):
        a, b = group_ptr[i], group_ptr[i + 1]
        pack_idx[i, : b - a] = np.arange(a, b)
        M_np[i, : b - a] = 1.0
        row_q[a:b] = i
        row_slot[a:b] = np.arange(b - a)
        covered_np[a:b] = True
    Y = jnp.asarray(np.asarray(y, np.float32)[pack_idx] * M_np)
    M = jnp.asarray(M_np)
    pack = jnp.asarray(pack_idx)
    rq, rs = jnp.asarray(row_q), jnp.asarray(row_slot)
    covered = jnp.asarray(covered_np)

    def fn(scores):
        S = scores[:, 0][pack] * M
        gain = (2.0 ** Y - 1.0) * M
        order = jnp.argsort(-jnp.where(M > 0, S, -jnp.inf), axis=1)
        ranks = jnp.argsort(order, axis=1).astype(jnp.float32)  # 0-based rank
        disc = 1.0 / jnp.log2(ranks + 2.0)
        ideal_gain = -jnp.sort(-gain, axis=1)
        ideal_disc = 1.0 / jnp.log2(jnp.arange(gmax, dtype=jnp.float32) + 2.0)
        idcg = jnp.sum(ideal_gain * ideal_disc, axis=1, keepdims=True)
        idcg = jnp.maximum(idcg, 1e-9)
        sdiff = S[:, :, None] - S[:, None, :]
        rho = 1.0 / (1.0 + jnp.exp(sigmoid * sdiff))      # P(j beats i)
        better = (Y[:, :, None] > Y[:, None, :]) & (M[:, :, None] > 0) & (M[:, None, :] > 0)
        delta_ndcg = jnp.abs(
            (gain[:, :, None] - gain[:, None, :]) *
            (disc[:, :, None] - disc[:, None, :])) / idcg[:, :, None]
        lam_ij = jnp.where(better, -sigmoid * rho * delta_ndcg, 0.0)
        hess_ij = jnp.where(better, sigmoid * sigmoid * rho * (1 - rho) * delta_ndcg, 0.0)
        G = jnp.sum(lam_ij, axis=2) - jnp.sum(lam_ij, axis=1)
        H = jnp.maximum(jnp.sum(hess_ij, axis=2) + jnp.sum(hess_ij, axis=1), 1e-16)
        # unpack by gather: row -> its (group, slot) cell; rows not covered
        # by group_ptr stay inert (g=0, h~0), matching the scatter unpack
        g_row = jnp.where(covered, G[rq, rs], 0.0)
        h_row = jnp.where(covered, H[rq, rs], 1e-16)
        return g_row[:, None], h_row[:, None]

    return instrumented_jit(fn, name="lightgbm.lambdarank_grads")


def lambdarank_grads(scores: np.ndarray, y: np.ndarray, group_ptr: np.ndarray,
                     sigmoid: float = 1.0, trunc: int = 30) -> Tuple[np.ndarray, np.ndarray]:
    """One-shot host-facing wrapper over ``make_lambdarank_grad_fn``."""
    import jax.numpy as jnp
    fn = make_lambdarank_grad_fn(y, group_ptr, sigmoid)
    g, h = fn(jnp.asarray(np.asarray(scores, np.float32).reshape(len(y), -1)))
    return np.asarray(g), np.asarray(h)


# ---------------------------------------------------------------------------
# jit caches: reusing compiled programs across train() calls saves the ~60-90s
# XLA compile on every fit (closures would otherwise defeat jit's cache)
# ---------------------------------------------------------------------------

_JIT_CACHE: Dict[tuple, object] = {}


def _params_sig(p: "GBDTParams") -> tuple:
    return (p.growth, p.num_leaves, p.max_depth, p.max_bin, p.objective,
            p.num_class, p.boosting_type,
            p.learning_rate, p.lambda_l1, p.lambda_l2, p.min_data_in_leaf,
            p.min_sum_hessian_in_leaf, p.min_gain_to_split, p.max_delta_step,
            p.sigmoid, p.alpha, p.tweedie_variance_power,
            p.top_rate, p.other_rate, p.feature_fraction,
            p.bagging_fraction, p.bagging_freq,
            tuple(p.categorical_features or ()), tuple(p.cat_subset or ()),
            p.max_cat_to_onehot, p.cat_smooth, p.cat_l2, p.max_cat_threshold,
            p.voting_k, p.use_quantized_grad, p.num_grad_quant_bins,
            # the quantizer's stochastic-rounding seed is baked into every
            # traced grower closure — without it in the key a second train()
            # with a different seed would silently reuse the old noise
            p.seed)


def _cached(key, builder):
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = builder()
        _JIT_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# tree grower
# ---------------------------------------------------------------------------

def _check_quant_psum_bound(use_quant: bool, quant_bins: int,
                            axis_name, psum_row_bound) -> None:
    """Sharded overflow guard: the quantized builders check int32 overflow
    against their LOCAL shard's rows, but the psum accumulates GLOBAL sums
    — a root-level cell can hold up to the total row count.  The grower
    knows the static global bound, so the check belongs here (review
    finding: 8 shards x 20M rows each passes every local guard yet wraps
    the hessian lane after the allreduce)."""
    if not use_quant or axis_name is None or psum_row_bound is None:
        return
    qh_cap = max(1, quant_bins - 1)
    if int(psum_row_bound) * qh_cap >= (1 << 31):
        raise ValueError(
            "quantized histograms overflow int32 after the cross-shard "
            f"psum above {(1 << 31) // qh_cap} total rows at "
            f"{quant_bins} quantization bins — lower num_grad_quant_bins "
            "or disable use_quantized_grad")


class _CatTools:
    """Categorical split machinery shared by both growers: static masks, the
    cat_l2-regularised score, ratio-sorted prefix stats (the many-vs-many
    candidate scan) and winner membership reconstruction.

    Reference: LightGBM's native sorted-subset categorical search, wired
    from ``LightGBMBase.scala:163-200`` (categoricalSlotIndexes ->
    ``categorical_feature`` engine param)."""

    def __init__(self, params: "GBDTParams", F: int, B: int):
        import jax.numpy as jnp
        self.jnp = jnp
        self.B = B
        self.cat_np = np.zeros((F,), bool)
        if params.categorical_features:
            self.cat_np[list(params.categorical_features)] = True
        self.sub_np = np.zeros((F,), bool)
        if params.cat_subset:
            self.sub_np[list(params.cat_subset)] = True
        self.has_cat = bool(self.cat_np.any())
        self.has_subset = bool(self.sub_np.any())
        self.cat_smooth = params.cat_smooth
        self.cat_l2 = params.cat_l2
        self.maxcat = float(params.max_cat_threshold)
        self.l1, self.l2 = params.lambda_l1, params.lambda_l2
        self.seenable_np = np.arange(B) != B - 1  # B-1 = NaN/overflow bin

    def leaf_score_cat(self, G, H):
        # subset splits score under extra regularisation (LightGBM cat_l2):
        # high-cardinality categoricals overfit the gain otherwise
        jnp = self.jnp
        t = jnp.sign(G) * jnp.maximum(jnp.abs(G) - self.l1, 0.0)
        return t ** 2 / (H + self.l2 + self.cat_l2)

    def sorted_prefix(self, hist_d):
        """Sorted-subset candidate stats for (..., B, 3) histograms: sort
        bins ascending by grad/hess ratio (cat_smooth in the denominator,
        LightGBM's categorical ordering); unseen bins and the NaN catch-all
        sort last (+inf), so the cumsum at position k is the stats of the
        BEST k+1 seen categories — the many-vs-many candidate set.  Returns
        (prefix_cumsum, sort_order, valid_prefix_mask)."""
        jnp, B = self.jnp, self.B
        seen = (hist_d[..., 2] > 0) & jnp.asarray(self.seenable_np)
        ratio = jnp.where(seen,
                          hist_d[..., 0] / (hist_d[..., 1] + self.cat_smooth),
                          jnp.inf)
        order = jnp.argsort(ratio, axis=-1)
        subcum = jnp.cumsum(
            jnp.take_along_axis(hist_d, order[..., None], axis=-2), axis=-2)
        nseen = seen.sum(axis=-1, keepdims=True).astype(jnp.float32)
        k1 = (jnp.arange(B) + 1).astype(jnp.float32)
        # a prefix must leave >=1 seen category right, and the smaller side
        # stays under max_cat_threshold (LightGBM's subset-size cap)
        sub_ok = (k1 < nseen) & ((k1 <= self.maxcat)
                                 | (nseen - k1 <= self.maxcat))
        return subcum, order, sub_ok

    def winner_member(self, win_hist, bf, bb):
        """(nodes, B) category membership of each node's winning split:
        subset winners take the first bb+1 bins of the ratio sort; one-vs-rest
        winners take the single code bb.  Only read where the winning feature
        is categorical."""
        jnp, B = self.jnp, self.B
        onehot_m = jnp.arange(B)[None, :] == bb[:, None]
        if not self.has_subset:
            return onehot_m
        _, ordw, _ = self.sorted_prefix(win_hist)
        msorted = jnp.arange(B)[None, :] <= bb[:, None]
        nodes = win_hist.shape[0]
        member_sub = jnp.zeros((nodes, B), bool).at[
            jnp.arange(nodes)[:, None], ordw].set(msorted)
        return jnp.where(jnp.asarray(self.sub_np)[bf][:, None], member_sub,
                         onehot_m)


def split_columns(binned, bf):
    """``(len(bf), n)`` int32: every row's bin in each of the columns ``bf``,
    by one product with their one-hot, which reads ``binned`` once and in
    place (the widening fuses into the operand).  Exact: every bin 0-255 is
    a bf16 value, and each output sums one such term.  (An int8 product of
    ``b ^ 0x80`` ties with it on a v5e: 3.0 ms at 5M x 200, PR 40.)"""
    import jax.numpy as jnp
    onehot = (jnp.arange(binned.shape[1]) == bf[:, None]).astype(jnp.bfloat16)
    return jnp.einsum("kf,nf->kn", onehot, binned.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32).astype(jnp.int32)


def make_tree_grower(max_depth: int, num_features: int, num_bins: int,
                     params: GBDTParams, axis_name: str = None,
                     backend: str = "auto", psum_row_bound: int = None):
    """Level-wise grower.  Returns grow(binned, grad, hess, hist_mask,
    feat_mask, edges) -> (left_child, right_child, split_feature, threshold,
    threshold_bin, split_gain, internal_value, internal_count, leaf_value,
    leaf_count, leaf_of_row).  With `axis_name`, the function is
    meant to run inside shard_map over row shards: local histograms are
    psum'd over that mesh axis (the LGBM_NetworkInit ring replacement) and
    all split decisions replicate deterministically across shards.
    ``psum_row_bound`` (sharded only) is the static GLOBAL row count, which
    lets the quantized path pack grad/hess lanes into one int32 channel for
    the allreduce when the bound allows (``collectives.histogram_psum``)."""
    import jax
    import jax.numpy as jnp
    from ..models.gbdt import perfect_tree_children
    from ..ops import histogram as hist_ops
    from ..parallel.collectives import histogram_psum

    use_quant = bool(params.use_quantized_grad)
    quant_bins = params.num_grad_quant_bins
    _check_quant_psum_bound(use_quant, quant_bins, axis_name, psum_row_bound)
    # where the histograms are MXU products, so is the routing's read of each
    # row's bin (``split_columns``): a per-row gather runs there as a scalar
    # loop, 59 ms a level at 5M rows (ROADMAP S3).  The CPU keeps the gather.
    route_by_product = hist_ops.xla_backend(backend) == "matmul"

    D, F, B = max_depth, num_features, num_bins
    I = 2 ** D - 1     # internal nodes
    L = 2 ** D         # leaves
    ct = _CatTools(params, F, B)
    cat_np, sub_np = ct.cat_np, ct.sub_np
    has_cat, has_subset = ct.has_cat, ct.has_subset
    sorted_prefix, winner_member = ct.sorted_prefix, ct.winner_member
    leaf_score_cat = ct.leaf_score_cat
    l1, l2 = params.lambda_l1, params.lambda_l2
    min_data = float(params.min_data_in_leaf)
    min_hess = params.min_sum_hessian_in_leaf
    min_gain = params.min_gain_to_split
    max_delta = params.max_delta_step

    def thresh(G):
        return jnp.sign(G) * jnp.maximum(jnp.abs(G) - l1, 0.0)

    def leaf_score(G, H):
        return thresh(G) ** 2 / (H + l2)

    def leaf_output(G, H):
        v = -thresh(G) / (H + l2)
        if max_delta > 0:
            v = jnp.clip(v, -max_delta, max_delta)
        return v

    def grow(binned, grad, hess, hist_mask, feat_mask, edges):
        n = binned.shape[0]
        if use_quant:
            # quantize ONCE per tree: every level's histogram is then an
            # exact integer function of the same per-row ints, so sibling
            # subtraction below never leaves integer space.  Sharded, the
            # rounding noise is keyed per GLOBAL row (elastic resume,
            # ISSUE 14): a row quantizes identically at any shard count,
            # which is what makes resume onto a re-sized mesh bit-exact.
            with jax.named_scope(PHASE_QUANTIZE):
                row_ids = hist_ops.global_row_ids(axis_name, n)
                qg, qh, g_scale, h_scale = hist_ops.quantize_gradients(
                    grad, hess, quant_bins, seed=params.seed,
                    axis_name=axis_name, row_ids=row_ids)

        def build_local(node_a, num_nodes, max_rows=None):
            # (the builders put their layout of the rows as node-pure blocks
            # under PHASE_LAYOUT themselves)
            with jax.named_scope(PHASE_HIST):
                if use_quant:
                    return hist_ops.build_quantized(
                        binned, qg, qh, node_a, num_nodes, num_bins,
                        quant_bins=quant_bins, backend=backend,
                        max_rows=max_rows, node_rows_bound=max_rows)
                return hist_ops.build(binned, grad, hess, node_a, num_nodes,
                                      num_bins, backend=backend,
                                      max_rows=max_rows)

        def allreduce(h_):
            with jax.named_scope(PHASE_ALLREDUCE):
                return histogram_psum(h_, axis_name,
                                      row_bound=psum_row_bound,
                                      quant_bins=quant_bins) \
                    if use_quant else jax.lax.psum(h_, axis_name)

        def hist(node_a, num_nodes, max_rows=None):
            out = build_local(node_a, num_nodes, max_rows=max_rows)
            if axis_name is not None:
                out = allreduce(out)
            return out

        def dehist(h_):
            # rescale integer sums to (grad, hess, count) floats — applied
            # only where gains/leaf stats are computed, never to the
            # subtraction chain
            if not use_quant:
                return h_
            with jax.named_scope(PHASE_HIST):
                return hist_ops.dequantize_histogram(h_, g_scale, h_scale)

        node = jnp.zeros((n,), jnp.int32)          # level-local node, all rows
        split_feature = jnp.full((I,), -1, jnp.int32)
        threshold_bin = jnp.zeros((I,), jnp.int32)
        threshold = jnp.zeros((I,), jnp.float32)
        split_gain = jnp.zeros((I,), jnp.float32)
        internal_value = jnp.zeros((I,), jnp.float32)
        internal_count = jnp.zeros((I,), jnp.float32)
        # per-internal-node category membership of the LEFT set (read only
        # where the split feature is categorical); 1-wide dummy otherwise
        cat_member = jnp.zeros((I, B if has_cat else 1), bool)

        cat_b = jnp.asarray(cat_np)
        sub_b = jnp.asarray(sub_np)
        edge_ok2 = jnp.concatenate(
            [jnp.isfinite(edges), jnp.zeros((F, 1), bool)], axis=1)
        edge_finite = edge_ok2[None, :, :]
        if has_cat:
            # every bin of a categorical feature is a candidate code EXCEPT
            # the last: BinMapper reserves bin max_bin-1 for NaN/overflow,
            # and a split on it would route missing rows left at train but
            # right at predict (x != code with NaN -> right)
            cat_cand = cat_b[None, :, None] & \
                (jnp.arange(B) != B - 1)[None, None, :]
            edge_finite = edge_finite | cat_cand
        def split_gains(hist_d, fmask2, edge3, catm2, subm2):
            """(nodes, Fs, B, 3) histograms -> (gain, left-stat pick, node
            totals).  LEFT-child stats: numerical split at t takes bins <= t
            (the cumsum); categorical one-vs-rest at code c takes bin c alone
            (the histogram itself); sorted-subset candidate k takes the best
            k+1 ratio-sorted categories (the prefix cumsum).  ``fmask2`` /
            ``catm2`` / ``subm2`` broadcast over (nodes, Fs); ``edge3`` over
            (nodes, Fs, B)."""
            cum = jnp.cumsum(hist_d, axis=2)
            tot = cum[:, :1, -1, :]                 # (nodes,1,3) totals
            left3 = jnp.where(catm2[:, :, None, None], hist_d, cum) \
                if has_cat else cum
            if has_subset:
                subcum, _, sub_ok = sorted_prefix(hist_d)
                left3 = jnp.where(subm2[:, :, None, None], subcum, left3)
                edge3 = jnp.where(subm2[:, :, None], sub_ok, edge3)
            GL, HL, CL = left3[..., 0], left3[..., 1], left3[..., 2]
            Gp, Hp, Cp = tot[..., 0], tot[..., 1], tot[..., 2]
            GR, HR, CR = (Gp[:, :, None] - GL, Hp[:, :, None] - HL,
                          Cp[:, :, None] - CL)
            gain = (leaf_score(GL, HL) + leaf_score(GR, HR)
                    - leaf_score(Gp, Hp)[:, :, None])
            if has_subset:
                gain_cat = (leaf_score_cat(GL, HL) + leaf_score_cat(GR, HR)
                            - leaf_score_cat(Gp, Hp)[:, :, None])
                gain = jnp.where(subm2[:, :, None], gain_cat, gain)
            # split at bin t => left: bins<=t, right: bins>t; needs a finite
            # edge (last bin and inf-padded pseudo-bins can't split)
            valid = ((CL >= min_data) & (CR >= min_data)
                     & (HL >= min_hess) & (HR >= min_hess)
                     & fmask2[:, :, None] & edge3)
            gain = jnp.where(valid, gain, -jnp.inf)
            pick = jnp.stack([GL, HL, CL], axis=-1)  # (nodes,Fs,B,3)
            return gain, pick, (Gp[:, 0], Hp[:, 0], Cp[:, 0])

        voting_k = params.voting_k
        # voting engages whenever it's requested and meaningful (F > k);
        # when 2k >= F the vote selects every feature — zero comm saving but
        # identical results, which the equality test exploits
        use_voting = axis_name is not None and 0 < voting_k < F
        prev_hist = None
        best_stats = None
        small_left = None      # set per level; read from the NEXT level on
        for d in range(D):
          # one scope per level outside the phase scopes: an operation's
          # path reads .../L3/gbdt.hist/dot_general
          with jax.named_scope(f"L{d}"):
            nodes_d = 2 ** d
            off = nodes_d - 1                       # BFS offset of this level
            if d > 0 and not use_voting:
                # LightGBM's SMALLER-child rule (by the previous level's
                # split counts): rebuild only each parent's smaller child,
                # sibling = parent - small.
                with jax.named_scope(PHASE_HIST):
                    is_left = node % 2 == 0
                    in_small = is_left == small_left[node // 2]
                    small_node = jnp.where(hist_mask & in_small, node // 2, -1)
            if use_voting:
                # voting-parallel (reference voting_parallel + topK): each
                # shard ranks features by LOCAL gain, shards vote, and only
                # the global top-2k features' histograms cross the mesh —
                # O(k*B) comm instead of O(F*B).  Sibling subtraction stays
                # valid on the PRE-psum local histograms (local_right =
                # local_parent - local_left).
                with jax.named_scope(PHASE_HIST):
                    if d == 0:
                        local = build_local(jnp.where(hist_mask, node, -1), 1)
                    else:
                        left_node = jnp.where(hist_mask & (node % 2 == 0),
                                              node // 2, -1)
                        left_local = build_local(left_node, nodes_d // 2)
                        local = jnp.stack(
                            [left_local, prev_hist - left_local],
                            axis=1).reshape(nodes_d, F, B, 3)
                prev_hist = local
                with jax.named_scope(PHASE_SPLIT):
                    gain_l, _, _ = split_gains(
                        dehist(local), feat_mask[None, :], edge_finite,
                        cat_b[None, :], sub_b[None, :])
                    per_feat = gain_l.max(axis=2)    # (nodes, F) local best
                    top_gain, top_local = jax.lax.top_k(per_feat, voting_k)
                    # a shard with fewer than k locally-valid candidates must
                    # not cast spurious ballots for the tie-broken low-index
                    # features
                    ballot = (top_gain > -jnp.inf).astype(jnp.float32)
                    votes = jnp.zeros((nodes_d, F)).at[
                        jnp.arange(nodes_d)[:, None], top_local].add(ballot)
                with jax.named_scope(PHASE_ALLREDUCE):
                    votes = jax.lax.psum(votes, axis_name)
                k2 = min(2 * voting_k, F)
                with jax.named_scope(PHASE_SPLIT):
                    _, sel = jax.lax.top_k(votes, k2)  # (nodes, k2) global pick
                    sel_hist = jnp.take_along_axis(
                        local, sel[:, :, None, None], axis=1)
                sel_hist = dehist(allreduce(sel_hist))
                with jax.named_scope(PHASE_SPLIT):
                    edge3 = jnp.take_along_axis(
                        jnp.broadcast_to(edge_finite, (nodes_d, F, B)),
                        sel[:, :, None], axis=1)
                    gain, pick, (Gp0, Hp0, Cp0) = split_gains(
                        sel_hist, feat_mask[sel], edge3, cat_b[sel],
                        sub_b[sel])
                hist_for_win = sel_hist
                Fs = k2
            else:
                if d == 0:
                    with jax.named_scope(PHASE_HIST):
                        root_node = jnp.where(hist_mask, node, -1)
                    hist_d = hist(root_node, 1)
                else:
                    # smaller-child scatter (small_node above): at most
                    # floor(n/2) rows are ever scattered, which — single-
                    # shard — is a STATIC bound that truncates the matmul
                    # backend's block scan to half the blocks (sharded: a
                    # shard's rows may concentrate in globally smaller
                    # children, so no bound is claimed there).
                    cap = None if axis_name is not None else n // 2 + nodes_d
                    hist_small = hist(small_node, nodes_d // 2, max_rows=cap)
                    with jax.named_scope(PHASE_HIST):
                        hist_sib = prev_hist - hist_small
                        sl4 = small_left[:, None, None, None]
                        hist_d = jnp.stack(
                            [jnp.where(sl4, hist_small, hist_sib),
                             jnp.where(sl4, hist_sib, hist_small)], axis=1) \
                            .reshape(nodes_d, F, B, 3)
                prev_hist = hist_d
                with jax.named_scope(PHASE_SPLIT):
                    gain, pick, (Gp0, Hp0, Cp0) = split_gains(
                        dehist(hist_d), feat_mask[None, :], edge_finite,
                        cat_b[None, :], sub_b[None, :])
                hist_for_win = dehist(hist_d)
                sel = None
                Fs = F

            with jax.named_scope(PHASE_SPLIT):
                flat = gain.reshape(nodes_d, Fs * B)
                best = jnp.argmax(flat, axis=1)
                best_gain = jnp.take_along_axis(flat, best[:, None],
                                                axis=1)[:, 0]
                bf_local = (best // B).astype(jnp.int32)
                bb = (best % B).astype(jnp.int32)
                bf = sel[jnp.arange(nodes_d), bf_local] \
                    if sel is not None else bf_local
                bsel = pick[jnp.arange(nodes_d), bf_local, bb, :]  # left
                do_split = best_gain > min_gain

                idx = off + jnp.arange(nodes_d)
                if has_cat:
                    member = winner_member(
                        hist_for_win[jnp.arange(nodes_d), bf_local], bf, bb)
                    cat_member = cat_member.at[idx].set(
                        member & do_split[:, None] & cat_b[bf][:, None])
                split_feature = split_feature.at[idx].set(
                    jnp.where(do_split, bf, -1))
                threshold_bin = threshold_bin.at[idx].set(bb)
                thr_raw = edges[bf, jnp.clip(bb, 0, B - 2)]
                if has_cat:  # categorical: the raw threshold IS the category code
                    thr_raw = jnp.where(cat_b[bf], bb.astype(jnp.float32),
                                        thr_raw)
                threshold = threshold.at[idx].set(thr_raw)
                split_gain = split_gain.at[idx].set(
                    jnp.where(do_split, best_gain, 0.0))
                internal_value = internal_value.at[idx].set(
                    leaf_output(Gp0, Hp0))
                internal_count = internal_count.at[idx].set(Cp0)

                # left/right child stats at the chosen split -> leaf values at
                # the last level come straight from here (no extra leaf pass)
                tot3 = jnp.stack([Gp0, Hp0, Cp0], axis=-1)
                left_stats = jnp.where(do_split[:, None], bsel, tot3)
                right_stats = tot3 - left_stats
                best_stats = (left_stats, right_stats, do_split, tot3)
                # the next level scatters only each parent's smaller child
                # (unsplit parents: right is empty -> small, contributing 0
                # rows)
                small_left = left_stats[:, 2] <= right_stats[:, 2]

            # route all rows (bagged-out rows too: they need leaf ids for scores)
            with jax.named_scope(PHASE_ROUTE):
                if route_by_product:
                    # each row picks its own node's lane: no per-row gather
                    mine = node == jnp.arange(nodes_d)[:, None]

                    def of_row(per_node):
                        return jnp.where(mine, per_node, 0).sum(0)
                    row_bin = of_row(split_columns(binned, bf))
                    t_of_row = of_row(bb[:, None])
                    s_of_row = (mine & do_split[:, None]).any(0)
                    if has_cat:
                        cat_of_row = (mine & cat_b[bf][:, None]).any(0)
                else:
                    f_of_row = jnp.maximum(bf[node], 0)
                    t_of_row = bb[node]
                    s_of_row = do_split[node]
                    row_bin = binned[jnp.arange(n),
                                     f_of_row].astype(jnp.int32)
                    if has_cat:
                        cat_of_row = cat_b[f_of_row]
                if has_cat:
                    memb_row = member[node, row_bin]
                    right_dec = jnp.where(cat_of_row, ~memb_row,
                                          row_bin > t_of_row)
                else:
                    right_dec = row_bin > t_of_row
                go_right = s_of_row & right_dec
                node = 2 * node + go_right.astype(jnp.int32)

        # leaves: children of the last level's nodes
        with jax.named_scope(PHASE_UPDATE):
            left_stats, right_stats, do_split, tot3 = best_stats
            lv = jnp.stack([leaf_output(left_stats[:, 0], left_stats[:, 1]),
                            leaf_output(right_stats[:, 0], right_stats[:, 1])],
                           axis=1).reshape(L)
            lc = jnp.stack([left_stats[:, 2], right_stats[:, 2]],
                           axis=1).reshape(L)
            leaf_value = jnp.where(lc > 0, lv, 0.0)
        return (lc_const, rc_const, split_feature, threshold, threshold_bin,
                split_gain, internal_value, internal_count, leaf_value, lc,
                cat_member, node)

    lc_np, rc_np = perfect_tree_children(D)
    lc_const = jnp.asarray(lc_np)
    rc_const = jnp.asarray(rc_np)
    return grow

def leafwise_store_dtype(n_bound, use_quant: bool, quant_bins: int):
    """Storage dtype for the leaf-wise grower's per-leaf histogram carry
    (the ``(L, F, B, 3)`` buffer sibling subtraction reads from).

    Quantized sums are bounded by the STATIC row bound: every cell holds at
    most ``n_bound * (quant_bins - 1)`` (hess lane — the widest; ``|qg|``
    sums and counts are smaller), so when that fits int16 the stored buffer
    halves with zero information loss — the arithmetic (build, psum,
    subtraction) stays int32 and only the carry narrows.  This is exactly
    the regime out-of-core tiling creates: small per-tile row bounds make
    the stored histograms the dominant resident tensor, and 2-bit gradients
    (``num_grad_quant_bins=4``) stretch the int16 window to ~10.9k rows.
    ``n_bound=None`` (sharded without a declared global bound) and float
    mode keep the wide dtypes.
    """
    import jax.numpy as jnp
    if not use_quant:
        return jnp.float32
    qh_cap = max(1, quant_bins - 1)
    if n_bound is not None and int(n_bound) * qh_cap < (1 << 15):
        return jnp.int16
    return jnp.int32


def make_leafwise_grower(num_leaves: int, depth_cap: int, num_features: int,
                         num_bins: int, params: GBDTParams,
                         axis_name: str = None, backend: str = "auto",
                         psum_row_bound: int = None):
    """Leaf-wise (best-first) grower — LightGBM's defining growth algorithm
    (reference exposes ``numLeaves`` default 31, ``LightGBMParams.scala:331``;
    the native engine grows by global best gain).

    One tree = ``lax.scan`` over ``num_leaves - 1`` split steps.  Per step:
    pick the live leaf with the global best stored gain, split it (left
    child keeps the leaf slot, right child takes slot ``step + 1`` —
    LightGBM's own leaf numbering), rebuild only the left child's histogram
    with one masked pass and derive the sibling by subtraction, then score
    both children's best candidate splits for later steps.  All state is
    fixed-shape; a step whose best gain fails ``min_gain_to_split`` becomes
    a no-op (every later step no-ops too, since the best gain is global).

    ``depth_cap`` > 0 forbids splits at that depth (LightGBM ``maxDepth``
    with leaf-wise growth).  With ``axis_name`` the histogram passes psum
    over the mesh axis; ``voting_k`` engages per-step feature voting
    (reference voting_parallel: only top-2k features' histograms cross the
    mesh).

    Returns grow(binned, grad, hess, hist_mask, feat_mask, edges) with the
    same output signature as the level-wise grower."""
    import jax
    import jax.numpy as jnp
    from ..ops import histogram as hist_ops
    from ..parallel.collectives import histogram_psum

    use_quant = bool(params.use_quantized_grad)
    quant_bins = params.num_grad_quant_bins
    _check_quant_psum_bound(use_quant, quant_bins, axis_name, psum_row_bound)
    L, M, F, B = num_leaves, num_leaves - 1, num_features, num_bins
    ct = _CatTools(params, F, B)
    cat_np, sub_np = ct.cat_np, ct.sub_np
    has_cat, has_subset = ct.has_cat, ct.has_subset
    l1, l2 = params.lambda_l1, params.lambda_l2
    min_data = float(params.min_data_in_leaf)
    min_hess = params.min_sum_hessian_in_leaf
    min_gain = params.min_gain_to_split
    max_delta = params.max_delta_step
    voting_k = params.voting_k
    use_voting = axis_name is not None and 0 < voting_k < F

    def thresh(G):
        return jnp.sign(G) * jnp.maximum(jnp.abs(G) - l1, 0.0)

    def leaf_score(G, H):
        return thresh(G) ** 2 / (H + l2)

    def leaf_output(G, H):
        v = -thresh(G) / (H + l2)
        if max_delta > 0:
            v = jnp.clip(v, -max_delta, max_delta)
        return v

    def grow(binned, grad, hess, hist_mask, feat_mask, edges):
        n = binned.shape[0]
        cat_b = jnp.asarray(cat_np)
        sub_b = jnp.asarray(sub_np)
        edge_ok = jnp.concatenate(
            [jnp.isfinite(edges), jnp.zeros((F, 1), bool)], axis=1)
        if has_cat:
            # bin max_bin-1 is the NaN/overflow catch-all; splitting on it
            # would route missing left at train but right at predict
            edge_ok = jnp.where(cat_b[:, None],
                                (jnp.arange(B) != B - 1)[None, :], edge_ok)

        if use_quant:
            # one quantization per tree — every per-leaf rebuild and every
            # sibling subtraction below runs on the same per-row integers.
            # Sharded: noise keyed per GLOBAL row (elastic resume, ISSUE
            # 14) so re-sized meshes quantize each row identically.
            row_ids = hist_ops.global_row_ids(axis_name, n)
            qg, qh, g_scale, h_scale = hist_ops.quantize_gradients(
                grad, hess, quant_bins, seed=params.seed, axis_name=axis_name,
                row_ids=row_ids)

        def local_hist(mask):
            if use_quant:
                return hist_ops.build_quantized(
                    binned, qg, qh, jnp.where(mask, 0, -1), 1, B,
                    quant_bins=quant_bins, backend=backend)[0]  # (F, B, 3)
            return hist_ops.build(binned, grad, hess,
                                  jnp.where(mask, 0, -1), 1, B,
                                  backend=backend)[0]          # (F, B, 3)

        def psum_hist(h_):
            return histogram_psum(h_, axis_name, row_bound=psum_row_bound,
                                  quant_bins=quant_bins) \
                if use_quant else jax.lax.psum(h_, axis_name)

        def dehist(h_):
            # integer sums -> (grad, hess, count) floats at gain time only
            if not use_quant:
                return h_
            return hist_ops.dequantize_histogram(h_, g_scale, h_scale)

        def candidate_tables(hist_f3, fmask, depth_ok):
            """(F, B) gains + left-child pick stats from one leaf's (psum'd)
            histogram.  Same split semantics as the level-wise grower:
            numerical split at bin t takes bins <= t left (the cumsum);
            categorical one-vs-rest at code c takes bin c alone;
            sorted-subset candidate k takes the best k+1 ratio-sorted
            categories (the prefix cumsum)."""
            cum = jnp.cumsum(hist_f3, axis=1)
            tot = cum[0, -1, :]                               # (3,)
            left3 = jnp.where(cat_b[:, None, None], hist_f3, cum) \
                if has_cat else cum
            sub_edge = None
            if has_subset:
                subcum, _, sub_ok = ct.sorted_prefix(hist_f3)
                left3 = jnp.where(sub_b[:, None, None], subcum, left3)
                sub_edge = sub_ok
            GL, HL, CL = left3[..., 0], left3[..., 1], left3[..., 2]
            GR, HR, CR = tot[0] - GL, tot[1] - HL, tot[2] - CL
            gain = (leaf_score(GL, HL) + leaf_score(GR, HR)
                    - leaf_score(tot[0], tot[1]))
            if has_subset:
                gain_cat = (ct.leaf_score_cat(GL, HL)
                            + ct.leaf_score_cat(GR, HR)
                            - ct.leaf_score_cat(tot[0], tot[1]))
                gain = jnp.where(sub_b[:, None], gain_cat, gain)
            valid = ((CL >= min_data) & (CR >= min_data)
                     & (HL >= min_hess) & (HR >= min_hess)
                     & fmask[:, None] & depth_ok)
            if has_subset:  # subset prefixes have their own validity mask
                valid = valid & jnp.where(sub_b[:, None], sub_edge, True)
            return jnp.where(valid, gain, -jnp.inf), left3, tot

        def leaf_member(win_hist_b3, bf, bb):
            """(B,) membership of one leaf's winning categorical split."""
            return ct.winner_member(win_hist_b3[None], bf[None],
                                    bb[None])[0]

        def leaf_best(hist_f3, fmask, depth_ok):
            """Best candidate split of one leaf: (gain, feat, bin,
            left-child (G,H,C), totals, member bitset).  Accepts raw (int
            in quantized mode) histograms and rescales here — gain math
            always runs on float sums."""
            hist_f3 = dehist(hist_f3)
            gain, left3, tot = candidate_tables(hist_f3, fmask, depth_ok)
            # edge_ok is sound for subset features too: their position-(B-1)
            # candidate (a prefix of all bins) is invalid regardless
            gain = jnp.where(edge_ok, gain, -jnp.inf)
            flat = gain.reshape(-1)
            best = jnp.argmax(flat)
            bf = (best // B).astype(jnp.int32)
            bb = (best % B).astype(jnp.int32)
            member = leaf_member(hist_f3[bf], bf, bb) if has_cat else None
            return flat[best], bf, bb, left3[bf, bb], tot, member

        def leaf_best_voting(hist_local_f3, fmask, depth_ok):
            """Voting-parallel per-leaf split finding: rank features by
            LOCAL gain, psum ballots, then psum only the global top-2k
            features' histogram slices (O(k*B) ICI traffic per step)."""
            gain_l, _, _ = candidate_tables(dehist(hist_local_f3), fmask,
                                            depth_ok)
            gain_l = jnp.where(edge_ok, gain_l, -jnp.inf)
            per_feat = gain_l.max(axis=1)                     # (F,)
            top_gain, top_idx = jax.lax.top_k(per_feat, voting_k)
            ballot = (top_gain > -jnp.inf).astype(jnp.float32)
            votes = jnp.zeros((F,)).at[top_idx].add(ballot)
            votes = jax.lax.psum(votes, axis_name)
            k2 = min(2 * voting_k, F)
            _, sel = jax.lax.top_k(votes, k2)                 # (k2,) features
            sel_hist = dehist(psum_hist(hist_local_f3[sel]))
            cum = jnp.cumsum(sel_hist, axis=1)
            tot = dehist(psum_hist(
                jnp.cumsum(hist_local_f3[:1], axis=1)[0, -1, :]))
            left3 = jnp.where(cat_b[sel][:, None, None], sel_hist, cum) \
                if has_cat else cum
            sub_edge = True
            if has_subset:
                subcum, _, sub_ok = ct.sorted_prefix(sel_hist)
                left3 = jnp.where(sub_b[sel][:, None, None], subcum, left3)
                sub_edge = jnp.where(sub_b[sel][:, None], sub_ok, True)
            GL, HL, CL = left3[..., 0], left3[..., 1], left3[..., 2]
            GR, HR, CR = tot[0] - GL, tot[1] - HL, tot[2] - CL
            gain = (leaf_score(GL, HL) + leaf_score(GR, HR)
                    - leaf_score(tot[0], tot[1]))
            if has_subset:
                gain_cat = (ct.leaf_score_cat(GL, HL)
                            + ct.leaf_score_cat(GR, HR)
                            - ct.leaf_score_cat(tot[0], tot[1]))
                gain = jnp.where(sub_b[sel][:, None], gain_cat, gain)
            valid = ((CL >= min_data) & (CR >= min_data)
                     & (HL >= min_hess) & (HR >= min_hess)
                     & fmask[sel][:, None] & depth_ok & edge_ok[sel]
                     & sub_edge)
            gain = jnp.where(valid, gain, -jnp.inf)
            flat = gain.reshape(-1)
            best = jnp.argmax(flat)
            bf = sel[(best // B)].astype(jnp.int32)
            bb = (best % B).astype(jnp.int32)
            # membership from the winner's GLOBAL (psum'd) histogram slice:
            # every shard reconstructs the identical bitset
            member = leaf_member(sel_hist[best // B], bf, bb) \
                if has_cat else None
            return flat[best], bf, bb, left3[best // B, bb], tot, member

        best_of = leaf_best_voting if use_voting else leaf_best

        def psum_maybe(x):
            # with voting, per-leaf stored histograms stay LOCAL (sibling
            # subtraction then remains exact on local stats); without it the
            # stored histograms are global
            if axis_name is not None and not use_voting:
                return psum_hist(x)
            return x

        def depth_ok_of(d):
            if depth_cap <= 0:
                return jnp.bool_(True)
            return d < depth_cap

        # ---- root
        leaf_of_row = jnp.zeros((n,), jnp.int32)
        h_root = psum_maybe(local_hist(hist_mask))
        g0, f0, b0, lp0, tot0, m0 = best_of(h_root, feat_mask,
                                            depth_ok_of(0))

        # stored-histogram carry dtype: int16 when the STATIC row bound
        # keeps every quantized cell under 15 bits (sums stay exact; the
        # arithmetic below is int32 and only the carry narrows).  The bound
        # is this shard's n when stored histograms are local (single-shard
        # or voting), the declared global psum bound when they are global.
        stored_bound = n if (axis_name is None or use_voting) \
            else psum_row_bound
        st_dtype = leafwise_store_dtype(stored_bound, use_quant, quant_bins)

        carry0 = dict(
            leaf_of_row=leaf_of_row,
            lc_arr=jnp.full((M,), -1, jnp.int32),
            rc_arr=jnp.full((M,), -1, jnp.int32),
            sf=jnp.full((M,), -1, jnp.int32),
            th=jnp.zeros((M,), jnp.float32),
            tb=jnp.zeros((M,), jnp.int32),
            sg=jnp.zeros((M,), jnp.float32),
            iv=jnp.zeros((M,), jnp.float32),
            ic=jnp.zeros((M,), jnp.float32),
            hists=jnp.zeros((L, F, B, 3), st_dtype)
            .at[0].set(h_root.astype(st_dtype)),
            best_gain=jnp.full((L,), -jnp.inf).at[0].set(g0),
            best_feat=jnp.zeros((L,), jnp.int32).at[0].set(f0),
            best_bin=jnp.zeros((L,), jnp.int32).at[0].set(b0),
            best_left=jnp.zeros((L, 3)).at[0].set(lp0),
            leaf_tot=jnp.zeros((L, 3)).at[0].set(tot0),
            leaf_depth=jnp.zeros((L,), jnp.int32),
            created=jnp.zeros((L,), bool).at[0].set(True),
            # per-internal-node LEFT category set + each live leaf's best
            # candidate's membership (1-wide dummies without categoricals)
            cbs=jnp.zeros((M, B if has_cat else 1), bool),
            best_member=(jnp.zeros((L, B), bool).at[0].set(m0) if has_cat
                         else jnp.zeros((L, 1), bool)),
        )

        def step(c, s):
            j = jnp.argmax(c["best_gain"]).astype(jnp.int32)
            gmax = c["best_gain"][j]
            do = gmax > min_gain
            new_leaf = (s + 1).astype(jnp.int32)
            f, b = c["best_feat"][j], c["best_bin"][j]

            def set_if(arr, idx, val, cond, oob):
                # conditional in-place update: a failed condition redirects
                # the index out of bounds, which mode="drop" discards
                return arr.at[jnp.where(cond, idx, oob)].set(val, mode="drop")

            tot = c["leaf_tot"][j]
            thr_raw = edges[f, jnp.clip(b, 0, B - 2)]
            if has_cat:
                thr_raw = jnp.where(cat_b[f], b.astype(jnp.float32), thr_raw)

            c = dict(c)
            if has_cat:
                member_j = c["best_member"][j]               # (B,)
                c["cbs"] = set_if(c["cbs"], s, member_j & cat_b[f], do, M)
            c["sf"] = set_if(c["sf"], s, f, do, M)
            c["tb"] = set_if(c["tb"], s, b, do, M)
            c["th"] = set_if(c["th"], s, thr_raw, do, M)
            c["sg"] = set_if(c["sg"], s, gmax, do, M)
            c["iv"] = set_if(c["iv"], s, leaf_output(tot[0], tot[1]), do, M)
            c["ic"] = set_if(c["ic"], s, tot[2], do, M)

            # re-point the parent edge that led to leaf j at internal node s
            pn = c["leaf_parent"][j]
            side = c["leaf_side"][j]
            c["lc_arr"] = set_if(c["lc_arr"], pn, s,
                                 do & (pn >= 0) & (side == 0), M)
            c["rc_arr"] = set_if(c["rc_arr"], pn, s,
                                 do & (pn >= 0) & (side == 1), M)
            # node s's own children: left keeps slot j, right takes new_leaf
            c["lc_arr"] = set_if(c["lc_arr"], s, -(j + 1), do, M)
            c["rc_arr"] = set_if(c["rc_arr"], s, -(new_leaf + 1), do, M)
            c["leaf_parent"] = set_if(c["leaf_parent"], j, s, do, L)
            c["leaf_side"] = set_if(c["leaf_side"], j, 0, do, L)
            c["leaf_parent"] = set_if(c["leaf_parent"], new_leaf, s, do, L)
            c["leaf_side"] = set_if(c["leaf_side"], new_leaf, 1, do, L)
            c["created"] = set_if(c["created"], new_leaf, True, do, L)

            # route rows of leaf j
            in_j = c["leaf_of_row"] == j
            row_bin = binned[jnp.arange(n), jnp.maximum(f, 0)].astype(jnp.int32)
            if has_cat:
                right_dec = jnp.where(cat_b[jnp.maximum(f, 0)],
                                      ~member_j[row_bin], row_bin > b)
            else:
                right_dec = row_bin > b
            c["leaf_of_row"] = jnp.where(do & in_j & right_dec, new_leaf,
                                         c["leaf_of_row"])

            # child stats + histograms (left rebuilt, right by subtraction)
            left_stats = c["best_left"][j]
            right_stats = tot - left_stats
            c["leaf_tot"] = set_if(c["leaf_tot"], j, left_stats, do, L)
            c["leaf_tot"] = set_if(c["leaf_tot"], new_leaf, right_stats, do, L)
            d_new = c["leaf_depth"][j] + 1
            c["leaf_depth"] = set_if(c["leaf_depth"], j, d_new, do, L)
            c["leaf_depth"] = set_if(c["leaf_depth"], new_leaf, d_new, do, L)

            dok = depth_ok_of(d_new)
            hl = local_hist(hist_mask & (c["leaf_of_row"] == j))
            if axis_name is not None and not use_voting:
                hl = psum_hist(hl)
            # subtraction widens back to the build dtype: the int16
            # carry is storage-only, the arithmetic stays exact int32
            hr = c["hists"][j].astype(hl.dtype) - hl
            gl, fl, bl, lpl, _, ml = best_of(hl, feat_mask, dok)
            gr, fr, br, lpr, _, mr = best_of(hr, feat_mask, dok)
            c["hists"] = set_if(c["hists"], j, hl.astype(st_dtype), do, L)
            c["hists"] = set_if(c["hists"], new_leaf, hr.astype(st_dtype),
                                do, L)
            if has_cat:
                c["best_member"] = set_if(c["best_member"], j, ml, do, L)
                c["best_member"] = set_if(c["best_member"], new_leaf, mr,
                                          do, L)
            c["best_gain"] = set_if(c["best_gain"], j, gl, do, L)
            c["best_gain"] = set_if(c["best_gain"], new_leaf, gr, do, L)
            c["best_feat"] = set_if(c["best_feat"], j, fl, do, L)
            c["best_feat"] = set_if(c["best_feat"], new_leaf, fr, do, L)
            c["best_bin"] = set_if(c["best_bin"], j, bl, do, L)
            c["best_bin"] = set_if(c["best_bin"], new_leaf, br, do, L)
            c["best_left"] = set_if(c["best_left"], j, lpl, do, L)
            c["best_left"] = set_if(c["best_left"], new_leaf, lpr, do, L)
            return c, None

        carry0["leaf_parent"] = jnp.full((L,), -1, jnp.int32)
        carry0["leaf_side"] = jnp.zeros((L,), jnp.int32)
        c, _ = jax.lax.scan(step, carry0, jnp.arange(M, dtype=jnp.int32))

        leaf_value = jnp.where(c["created"],
                               leaf_output(c["leaf_tot"][:, 0],
                                           c["leaf_tot"][:, 1]), 0.0)
        leaf_count = jnp.where(c["created"], c["leaf_tot"][:, 2], 0.0)
        return (c["lc_arr"], c["rc_arr"], c["sf"], c["th"], c["tb"], c["sg"],
                c["iv"], c["ic"], leaf_value, leaf_count, c["cbs"],
                c["leaf_of_row"])

    return grow


# ---------------------------------------------------------------------------
# binned tree walk (for incremental valid scoring / DART drop replay)
# ---------------------------------------------------------------------------

def make_binned_walker(depth_bound: int,
                       categorical_features: Optional[Tuple[int, ...]] = None):
    """Binned-space pointer-chase over array-of-nodes trees (leaf slots
    encoded ``~leaf_id``; leaves self-loop so a static ``depth_bound``
    iteration count resolves every tree shape).  ``bitset`` (M, B) carries
    sorted-subset categorical membership (bin in set -> left); without it,
    categorical nodes fall back to one-vs-rest code equality."""
    import jax
    import jax.numpy as jnp
    D = max(1, depth_bound)
    cats = frozenset(categorical_features or ())

    def walk(binned, split_feature, threshold_bin, left_child, right_child,
             bitset=None):
        n = binned.shape[0]
        node = jnp.zeros((n,), jnp.int32)
        F = binned.shape[1]
        cat_b = jnp.asarray(np.isin(np.arange(F), list(cats))) if cats else None
        for _ in range(D):
            j = jnp.maximum(node, 0)
            f = split_feature[j]
            t = threshold_bin[j]
            row_bin = binned[jnp.arange(n), jnp.maximum(f, 0)].astype(jnp.int32)
            if cat_b is not None:
                left_dec = bitset[j, row_bin] if bitset is not None \
                    else row_bin == t
                dec = jnp.where(cat_b[jnp.maximum(f, 0)], ~left_dec,
                                row_bin > t)
            else:
                dec = row_bin > t
            go_right = (f >= 0) & dec
            child = jnp.where(go_right, right_child[j], left_child[j])
            node = jnp.where(node >= 0, child, node)
        return ~node

    return instrumented_jit(walk, name="lightgbm.tree_walk")


# ---------------------------------------------------------------------------
# metrics (reference: core/metrics/MetricConstants.scala registry)
# ---------------------------------------------------------------------------

def _metric_binary_logloss(y, raw, w=None):
    p = 1.0 / (1.0 + np.exp(-raw[:, 0]))
    p = np.clip(p, 1e-15, 1 - 1e-15)
    ll = -(y * np.log(p) + (1 - y) * np.log(1 - p))
    return float(np.average(ll, weights=w))


def _metric_auc(y, raw, w=None):
    s = raw[:, 0]
    order = np.argsort(s)
    y_s = y[order]
    w_s = np.ones_like(y_s, dtype=np.float64) if w is None else np.asarray(w)[order]
    pos = (y_s > 0).astype(np.float64) * w_s
    neg = (1.0 - (y_s > 0)) * w_s
    cum_neg = np.cumsum(neg)
    auc = float(np.sum(pos * (cum_neg - 0.5 * neg)) /
                max(1e-12, np.sum(pos) * np.sum(neg)))
    return auc


def _metric_multi_logloss(y, raw, w=None):
    z = raw - raw.max(axis=1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(axis=1, keepdims=True)
    p = np.clip(p[np.arange(len(y)), y.astype(int)], 1e-15, None)
    return float(np.average(-np.log(p), weights=w))


def _metric_l2(y, raw, w=None):
    return float(np.average((raw[:, 0] - y) ** 2, weights=w))


def _metric_rmse(y, raw, w=None):
    return math.sqrt(_metric_l2(y, raw, w))


def _metric_l1(y, raw, w=None):
    return float(np.average(np.abs(raw[:, 0] - y), weights=w))


def _metric_poisson_nll(y, raw, w=None):
    mu = np.exp(np.clip(raw[:, 0], -30, 30))
    return float(np.average(mu - y * np.log(np.maximum(mu, 1e-12)), weights=w))


def _metric_gamma_nll(y, raw, w=None):
    s_ = np.clip(raw[:, 0], -30, 30)
    return float(np.average(s_ + y * np.exp(-s_), weights=w))


def _metric_pinball(y, raw, alpha, w=None):
    e = y - raw[:, 0]
    return float(np.average(np.maximum(alpha * e, (alpha - 1.0) * e),
                            weights=w))


def _metric_tweedie_nll(y, raw, rho, w=None):
    """Tweedie deviance NLL with log link (raw = log mean), 1 < rho < 2."""
    s_ = np.clip(raw[:, 0], -30, 30)
    nll = (-y * np.exp((1.0 - rho) * s_) / (1.0 - rho)
           + np.exp((2.0 - rho) * s_) / (2.0 - rho))
    return float(np.average(nll, weights=w))


METRICS = {"binary_logloss": (_metric_binary_logloss, False),
           "poisson_nll": (_metric_poisson_nll, False),
           "gamma_nll": (_metric_gamma_nll, False),
           "auc": (_metric_auc, True),
           "multi_logloss": (_metric_multi_logloss, False),
           "l2": (_metric_l2, False), "mse": (_metric_l2, False),
           "rmse": (_metric_rmse, False), "l1": (_metric_l1, False),
           "mae": (_metric_l1, False)}


def resolve_metric(metric_name: str, p: "GBDTParams"):
    """(metric_fn, larger_better) for a requested or default metric name.
    tweedie_nll is parameterized by the variance power, so it resolves to a
    closure here instead of living in METRICS; unknown names fall back to
    the objective's default (and that fallback handles tweedie too)."""
    def closures(name):
        if name == "tweedie_nll":
            rho_m = p.tweedie_variance_power
            return (lambda y_, raw_, w_=None:
                    _metric_tweedie_nll(y_, raw_, rho_m, w_), False)
        if name == "pinball":
            a_m = p.alpha
            return (lambda y_, raw_, w_=None:
                    _metric_pinball(y_, raw_, a_m, w_), False)
        return None

    got = closures(metric_name)
    if got is not None:
        return got
    if metric_name in METRICS:
        return METRICS[metric_name]
    fallback = default_metric(p.objective)
    got = closures(fallback)
    if got is not None:
        return got
    return METRICS.get(fallback, METRICS["l2"])


def default_metric(objective: str) -> str:
    return {"binary": "binary_logloss", "multiclass": "multi_logloss",
            "regression": "l2", "regression_l1": "l1", "huber": "l2",
            "quantile": "pinball", "lambdarank": "l2",
            "poisson": "poisson_nll", "tweedie": "tweedie_nll",
            "gamma": "gamma_nll"}.get(objective, "l2")


# ---------------------------------------------------------------------------
# training driver
# ---------------------------------------------------------------------------

def _resolve_hist_path(p: GBDTParams) -> Tuple[str, bool]:
    """(backend, quantized) one training call builds its histograms with:
    the platform's builder family, and ``use_quantized_grad`` where the
    caller set it, else packed integers on the TPU.  Resolved ONCE per
    call and passed down; the backend is part of every jit cache key (the
    params signature already carries the quantization)."""
    quantized = p.use_quantized_grad
    if quantized is None:
        quantized = platform() != "cpu"
    return xla_backend(), bool(quantized)


def _make_grower(p: GBDTParams, F: int, B: int, axis_name: str = None,
                 backend: str = "auto", psum_row_bound: int = None):
    """Growth-mode dispatch (call with resolved params)."""
    if p.growth == "leaf":
        return make_leafwise_grower(p.num_leaves, p.max_depth, F, B, p,
                                    axis_name=axis_name, backend=backend,
                                    psum_row_bound=psum_row_bound)
    return make_tree_grower(p.max_depth, F, B, p, axis_name=axis_name,
                            backend=backend, psum_row_bound=psum_row_bound)


@dataclasses.dataclass
class TrainResult:
    booster: GBDTBooster
    evals: List[Dict[str, float]]
    bin_mapper: BinMapper
    # out-of-core runs attach streaming diagnostics (tile geometry +
    # prefetch-overlap accounting); in-memory train() leaves it None
    extras: Optional[Dict[str, float]] = None


def _content_fingerprint(arr: np.ndarray) -> int:
    """Cheap strided content hash for cache keys: crc32 over ~4k strided
    elements.  Catches in-place mutation of a cached array that id()/shape
    keys alone cannot, at O(4k) cost regardless of array size.  Mutations
    confined to the skipped strides are (by design) not detected — it is a
    guard rail, not a cryptographic digest."""
    import zlib
    if arr.size == 0:
        return 0
    step = max(1, arr.size // 4096)
    # arr.flat[::step] materializes ONLY the ~4k sampled elements; ravel()
    # would copy the whole array whenever it is not C-contiguous
    sample = arr.flat[::step]
    return zlib.crc32(np.ascontiguousarray(sample).tobytes())


def train(X: np.ndarray, y: np.ndarray, params: GBDTParams,
          sample_weight: Optional[np.ndarray] = None,
          valid: Optional[Tuple[np.ndarray, np.ndarray]] = None,
          group_ptr: Optional[np.ndarray] = None,
          init_booster: Optional[GBDTBooster] = None,
          feature_names: Optional[List[str]] = None,
          callbacks: Optional[List[Callable]] = None,
          shard_rows: bool = False,
          bin_cache: Optional[Dict] = None,
          checkpoint_dir: Optional[str] = None,
          checkpoint_every: int = 0,
          checkpoint_keep_last: int = 3,
          resume: str = "auto",
          monitor_port: Optional[int] = None,
          monitor_stall_timeout_s: Optional[float] = None) -> TrainResult:
    """Boosting loop.  Host python drives iterations; each tree is one jitted
    XLA program (reference: driver drives ``updateOneIteration`` per iter,
    ``TrainUtils.scala:67``).  ``shard_rows`` puts the binned matrix/gradients
    row-sharded over the active mesh's data axis (GSPMD psums histograms over
    ICI — the allreduce-ring replacement).

    ``bin_cache`` contract: the memo is keyed on ``(id(X), shape, strided
    content fingerprint, binning params)``.  Rebinding a NEW array reuses
    nothing; mutating X IN PLACE between calls is detected by the ~4k-element
    strided fingerprint and rebins — but a mutation that only touches
    elements the stride skips can slip through, so callers that rewrite X
    wholesale should pass a fresh cache dict rather than rely on detection.

    Fault tolerance (ISSUE 10): with ``checkpoint_dir`` set, the run
    snapshots its booster-so-far + iteration + host PRNG/bagging state
    atomically every ``checkpoint_every`` iterations (plus once at the end)
    — the snapshot arrays are handed to a background writer thread as
    device-array references, so the device-to-host fetch AND the disk
    write both happen off the boosting loop.  ``resume="auto"`` restores
    the newest valid snapshot and continues through the warm-start
    machinery (a torn newest snapshot falls back to the previous one);
    SIGTERM/SIGINT requests one final checkpoint at the next iteration
    boundary and returns the partial booster cleanly with
    ``extras["preempted"]`` set.  ``resume="must"`` raises when no usable
    snapshot exists (restart scripts must not silently retrain from
    zero).

    Elastic resume (ISSUE 14): the snapshot records a topology stanza —
    device count, mesh shape, shard count — that is allowed to differ on
    restore.  A ``shard_rows`` run resumed on a re-sized mesh re-pads the
    row stream and bagging mask and re-keys the ``histogram_psum`` lane
    bound on the new width; with quantized histograms the per-row
    rounding noise is keyed by GLOBAL row id, so the resumed booster is
    bit-identical to an uninterrupted run at either width (tested shrink
    and grow).  The change books ``mmlspark_reshard_total`` and sets
    ``extras["resharded"]``.

    Live monitoring (ISSUE 19): ``monitor_port`` (0 = ephemeral) serves
    ``GET /progress`` / ``/metrics`` / ``/debug/{dump,profile}`` for the
    duration of the loop, and either monitor arg arms a stall watchdog
    (no iteration within max(4x EWMA iteration time,
    ``monitor_stall_timeout_s``) books ``mmlspark_training_stalls_total``
    and writes a ``trigger="train_stall"`` flight dump); see
    docs/OBSERVABILITY.md "Training plane"."""
    import jax
    import jax.numpy as jnp
    from ..observability import get_registry
    from ..observability.tracing import (Span, ambient_phase, current_span,
                                         export_span)

    # training-phase telemetry: per-iteration observations into the global
    # registry + ONE lightgbm.train span (child of the ambient fit span)
    # carrying phase totals.  Timings are on the host's clock round
    # asynchronous dispatches — no block_until_ready() syncs are inserted,
    # the hot loop stays async — so a device phase (histogram_split_update,
    # gradients, histogram_split, update) reads the time to ENQUEUE it, not
    # the device's; device time per phase is DEVICE_PHASES' in a trace.
    _phase_h = get_registry().histogram(
        "mmlspark_lightgbm_phase_seconds",
        "per-iteration training phase timings on the host's clock: for a "
        "device phase the enqueue time of an asynchronous dispatch, not "
        "device time",
        labels=("phase", "backend", "quantized"))
    _phase_totals: Dict[str, float] = {}

    def _observe_phase(phase: str, seconds: float, times: int = 1) -> None:
        # exemplar: every phase bucket keeps the training trace id, so a
        # slow-iteration outlier on /metrics resolves to this fit's trace;
        # backend/quantized labels make A/B runs attributable on /metrics
        for _ in range(times):
            _phase_h.observe(seconds, _train_span.trace_id, phase=phase,
                             backend=hist_backend,
                             quantized="1" if p.use_quantized_grad else "0")
        _phase_totals[phase] = _phase_totals.get(phase, 0.0) + seconds * times

    _parent_span = current_span()
    _train_span = Span(
        "lightgbm.train",
        trace_id=_parent_span.trace_id if _parent_span else None,
        parent_id=_parent_span.span_id if _parent_span else None)

    p = params.resolve()
    # histogram backend + quantization resolution, up front so every phase
    # observation below carries the effective (backend, quantized) labels
    hist_backend, _uq = _resolve_hist_path(p)
    p = dataclasses.replace(p, use_quantized_grad=_uq)
    rng = np.random.default_rng(p.seed)
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float32)
    n, F = X.shape
    K = p.num_class if p.objective == "multiclass" else 1
    w = np.ones(n, np.float32) if sample_weight is None else np.asarray(sample_weight, np.float32)

    if p.categorical_features:
        bad = [i for i in p.categorical_features if not 0 <= int(i) < F]
        if bad:
            raise ValueError(f"categorical_features indices {bad} out of "
                             f"range [0, {F}) — negative indices are not "
                             f"interpreted pythonically")
    if p.objective in ("poisson", "tweedie") and (y < 0).any():
        raise ValueError(f"objective {p.objective!r} requires non-negative "
                         f"labels (min label {float(y.min())})")
    if p.objective == "gamma" and (y <= 0).any():
        raise ValueError("objective 'gamma' requires strictly positive "
                         f"labels (min label {float(y.min())})")
    if p.objective == "tweedie" and not 1.0 < p.tweedie_variance_power < 2.0:
        raise ValueError(
            f"tweedie_variance_power must be in (1, 2), got "
            f"{p.tweedie_variance_power}; use objective='poisson' for the "
            f"rho=1 limit")
    # opt-in binning memo (bench/tuner: many train() calls over the SAME X
    # with fresh labels — quantile fit + digitize depend on X only).  The
    # dict pins X itself so the id() key can never be recycled by a
    # freed-and-reallocated array, and a signature miss drops EVERY derived
    # entry (incl. the device buffer) before repopulating.
    _bin_sig = (id(X), X.shape, _content_fingerprint(X), p.max_bin,
                tuple(p.categorical_features or ()))
    if bin_cache is not None and bin_cache.get("sig") == _bin_sig:
        mapper = bin_cache["mapper"]
        binned_np = bin_cache["binned"]
    else:
        _t_bin = time.perf_counter()
        with ambient_phase("lightgbm.binning"):
            mapper = BinMapper(
                p.max_bin,
                categorical_features=p.categorical_features).fit(X)
            binned_np = mapper.transform(X)
        _observe_phase("binning", time.perf_counter() - _t_bin)
        if bin_cache is not None:
            bin_cache.clear()
            bin_cache.update(sig=_bin_sig, X=X, mapper=mapper,
                             binned=binned_np)
    edges = jnp.asarray(mapper.edges)
    B = mapper.num_bins

    if p.categorical_features and p.cat_subset is None:
        # observed-cardinality mode split (LightGBM max_cat_to_onehot):
        # low-cardinality features stay one-vs-rest; the rest get the
        # sorted-subset many-vs-many search.  Data-dependent, hence part of
        # the resolved params (and the jit cache key).
        sub = []
        for f_i in p.categorical_features:
            codes = np.unique(binned_np[:, f_i])
            if int((codes != B - 1).sum()) > p.max_cat_to_onehot:
                sub.append(int(f_i))
        p = dataclasses.replace(p, cat_subset=tuple(sub))

    sig = _params_sig(p) + (hist_backend,)

    # ---- fault tolerance (ISSUE 10/14): periodic atomic checkpoints +
    # resume through the warm-start machinery below.  The fingerprint is
    # the DATA/PARAMS identity only (must match); topology — device
    # count, mesh shape, shard count — rides a separate recorded stanza
    # that is allowed to differ, because the fleet a preempted run
    # restarts on is rarely the fleet it lost (elastic resume).
    import contextlib
    from ..io.checkpoint import (CheckpointManager, book_reshard,
                                 check_resume_arg, resume_required_error,
                                 topology_stanza)
    from ..utils.resilience import PreemptionToken, preemption_scope
    _ckpt_fingerprint = repr((sig, n, F, B, K, shard_rows,
                              _content_fingerprint(X)))
    _topo_mesh = None
    if shard_rows:
        from ..parallel import get_active_mesh as _gam
        from ..parallel.mesh import AXIS_DATA as _AXIS_DATA
        _topo_mesh = _gam()
        _cur_topology = topology_stanza(
            mesh=_topo_mesh,
            shard_count=int(_topo_mesh.shape[_AXIS_DATA]))
    else:
        _cur_topology = topology_stanza(shard_count=1, device_count=1)
    check_resume_arg(resume, checkpoint_dir=checkpoint_dir)
    _mgr = None
    if checkpoint_dir:
        _mgr = CheckpointManager(checkpoint_dir, site="lightgbm.train",
                                 keep_last=checkpoint_keep_last)
    _resume_meta = None
    _resume_bag: Optional[np.ndarray] = None
    _resharded = False
    _n_user_init_trees = init_booster.num_trees if init_booster is not None \
        else 0
    if _mgr is not None and resume in ("auto", "must"):
        _got = _mgr.load_latest(current_topology=_cur_topology)
        if _got is None and resume == "must":
            raise resume_required_error(checkpoint_dir)
        if _got is not None:
            _, _arrs, _meta = _got
            if _meta.get("fingerprint") != _ckpt_fingerprint:
                raise ValueError(_CKPT_FINGERPRINT_MISMATCH)
            _delta = _meta.get("topology_delta")
            if _delta is not None and _delta["changed"]:
                # re-sharding: the row stream re-partitions onto the new
                # mesh width below (padding, bag mask, psum lane bound all
                # re-key on it) — book the delta so the resume is visible
                book_reshard("lightgbm.train", _delta)
                _resharded = True
            from ..models.gbdt import children_depth_bound
            # the snapshot booster replaces any user init_booster: it
            # already CONTAINS those trees (they were replayed into the
            # run the snapshot came from)
            init_booster = GBDTBooster(
                np.asarray(_arrs["split_feature"]),
                np.asarray(_arrs["threshold"]),
                np.asarray(_arrs["threshold_bin"]),
                np.asarray(_arrs["split_gain"]),
                np.asarray(_arrs["internal_value"]),
                np.asarray(_arrs["internal_count"]),
                np.asarray(_arrs["leaf_value"]),
                np.asarray(_arrs["leaf_count"]),
                np.asarray(_arrs["tree_weight"], np.float32),
                left_child=np.asarray(_arrs["left_child"]),
                right_child=np.asarray(_arrs["right_child"]),
                max_depth=children_depth_bound(_arrs["left_child"],
                                               _arrs["right_child"]),
                num_features=F, objective=p.objective, num_class=K,
                init_score=float(_meta["init_score"]),
                average_output=(p.boosting_type == "rf"),
                sigmoid=p.sigmoid,
                categorical_features=list(p.categorical_features or []),
                cat_bitset=(np.asarray(_arrs["cat_bitset"], bool)
                            if "cat_bitset" in _arrs else None))
            _n_user_init_trees = int(_meta.get("n_init_trees", 0))
            if "bag_mask" in _arrs:
                # unpacked at the restore site below: shard_rows pads n
                # between here and there
                _resume_bag = np.asarray(_arrs["bag_mask"])
            _resume_meta = _meta

    n_data = n           # pre-pad row count: host stats and the bagging
    y_data, w_data = y, w  # draw must be independent of the mesh width
    if shard_rows:
        from jax.sharding import PartitionSpec as P
        from ..parallel import batch_sharded
        from ..parallel.mesh import AXIS_DATA
        from ..parallel.sharding import pad_to_multiple
        mesh = _topo_mesh
        nd = mesh.shape[AXIS_DATA]
        binned_np, n_valid_rows = pad_to_multiple(binned_np, nd)
        y_pad, _ = pad_to_multiple(y, nd)
        w_pad, _ = pad_to_multiple(w, nd)
        w_pad[n_valid_rows:] = 0.0  # padded rows carry zero weight everywhere
        y, w = y_pad, w_pad
        n = binned_np.shape[0]
        sharding = batch_sharded(mesh)
        from ..observability.compute import device_put as _obs_device_put
        binned = _obs_device_put(binned_np, sharding,
                                 site="lightgbm.binned_shards")

        # explicit SPMD: each shard builds local histograms, psum over ICI
        def _build_sharded():
            # psum_row_bound = GLOBAL padded rows: the quantized path sizes
            # its packed allreduce lanes from it, so it is baked into the
            # closure — hence n in the cache key below
            grow_raw = _make_grower(p, F, B, axis_name=AXIS_DATA,
                                    backend=hist_backend, psum_row_bound=n)
            return instrumented_jit(jax.shard_map(
                grow_raw, mesh=mesh,
                in_specs=(P(AXIS_DATA), P(AXIS_DATA), P(AXIS_DATA), P(AXIS_DATA),
                          P(), P()),
                out_specs=(P(),) * 11 + (P(AXIS_DATA),), check_vma=False),
                name="lightgbm.sharded_grower")
        grower = _cached(("sharded_grower", sig, F, id(mesh), n),
                         _build_sharded)
    else:
        # the 200MB-at-bench-shape uint8 device put rides the memo too: the
        # device buffer is immutable to the trainer, so reuse is safe
        if bin_cache is not None and "binned_dev" in bin_cache \
                and bin_cache.get("sig") == _bin_sig:
            binned = bin_cache["binned_dev"]
        else:
            binned = jnp.asarray(binned_np)
            if bin_cache is not None:
                bin_cache["binned_dev"] = binned
        grower = _cached(("grower", sig, F),
                         lambda: instrumented_jit(
                             _make_grower(p, F, B, backend=hist_backend),
                             name="lightgbm.grower"))
    objective = make_objective(p)
    D = p.depth_bound                 # static walk bound during training
    L = p.num_leaves                  # leaf slots (level-wise: 2^max_depth)

    # init score (BoostFromAverage analogue) — computed on the UNPADDED
    # arrays: the padded tail is zero-weighted either way, but a pairwise
    # host sum over a width-dependent padded length would make the base
    # score (and so every f32 score after it) drift across mesh widths,
    # breaking elastic resume's bit-identity (ISSUE 14)
    init_score = 0.0
    if p.objective == "binary":
        pbar = float(np.clip(np.average(y_data, weights=w_data),
                             1e-6, 1 - 1e-6))
        init_score = math.log(pbar / (1 - pbar)) / p.sigmoid
    elif p.objective in ("regression", "huber"):
        init_score = float(np.average(y_data, weights=w_data))
    elif p.objective in ("poisson", "tweedie", "gamma"):  # log link
        init_score = float(np.log(max(np.average(y_data, weights=w_data),
                                      1e-9)))
    elif p.objective == "regression_l1":
        init_score = float(np.median(y_data))

    scores = jnp.full((n, K), init_score, jnp.float32)
    y_dev = jnp.asarray(y)
    w_dev = jnp.asarray(w)

    # warm start: replay existing booster on binned data
    _TREE_KEYS = ("left_child", "right_child", "split_feature", "threshold",
                  "threshold_bin", "split_gain", "internal_value",
                  "internal_count", "leaf_value", "leaf_count")
    has_cat = bool(p.categorical_features)
    # subset splits need the per-node category bitset persisted; a warm-start
    # booster that carries bitsets keeps them through continuation too
    store_bitset = has_cat and (
        bool(p.cat_subset)
        or (init_booster is not None
            and getattr(init_booster, "cat_bitset", None) is not None))
    tree_keys = _TREE_KEYS + (("cat_bitset",) if store_bitset else ())
    trees: Dict[str, List[np.ndarray]] = {k: [] for k in tree_keys}
    tree_weights: List[float] = []
    # the replay walker must also resolve warm-start trees, which may be
    # DEEPER than this run's depth bound (e.g. uncapped leaf-wise booster
    # continued with a capped run): truncating their walk would gather from
    # a negative pseudo-leaf and silently corrupt every later gradient
    walk_bound = max(D, init_booster.max_depth if init_booster is not None else 0)
    walker = _cached(("walker", walk_bound, tuple(p.categorical_features or ())),
                     lambda: make_binned_walker(walk_bound,
                                                p.categorical_features))
    if init_booster is not None:
        assert init_booster.num_leaves == L and init_booster.num_features == F
        # one-vs-rest warm-start trees get onehot bitsets synthesized so the
        # continued booster's trees are uniform
        init_cbs = init_booster.resolve_cat_bitset(B) if store_bitset else None
        for t in range(init_booster.num_trees):
            for k in _TREE_KEYS:
                trees[k].append(getattr(init_booster, k)[t])
            if store_bitset:
                trees["cat_bitset"].append(init_cbs[t])
            tree_weights.append(float(init_booster.tree_weight[t]))
            leaf = walker(binned, jnp.asarray(init_booster.split_feature[t]),
                          jnp.asarray(init_booster.threshold_bin[t]),
                          jnp.asarray(init_booster.left_child[t]),
                          jnp.asarray(init_booster.right_child[t]),
                          bitset=(jnp.asarray(init_cbs[t])
                                  if store_bitset else None))
            contrib = jnp.asarray(init_booster.leaf_value[t])[leaf] * init_booster.tree_weight[t]
            scores = scores.at[:, t % K].add(contrib)
        # shift base score to the incoming booster's BEFORE reassigning, so
        # continued training optimizes against the recorded init_score
        scores = scores + (init_booster.init_score - init_score)
        init_score = init_booster.init_score

    metric_name = p.metric or default_metric(p.objective)
    metric_fn, larger_better = resolve_metric(metric_name, p)
    evals: List[Dict[str, float]] = []
    has_valid = valid is not None
    if has_valid:
        Xv = np.asarray(valid[0], np.float32)
        yv = np.asarray(valid[1], np.float32)
        binned_v = jnp.asarray(mapper.transform(Xv))
        scores_v = jnp.full((Xv.shape[0], K), init_score, jnp.float32)
        if _resume_meta is not None and init_booster is not None:
            # resumed run: valid scores must carry the contributions of
            # the trees grown BEFORE the crash (user warm-start trees stay
            # out, matching the uninterrupted run's scores_v history)
            init_cbs_v = init_booster.resolve_cat_bitset(B) \
                if store_bitset else None
            for t in range(_n_user_init_trees, init_booster.num_trees):
                leaf_v = walker(binned_v,
                                jnp.asarray(init_booster.split_feature[t]),
                                jnp.asarray(init_booster.threshold_bin[t]),
                                jnp.asarray(init_booster.left_child[t]),
                                jnp.asarray(init_booster.right_child[t]),
                                bitset=(jnp.asarray(init_cbs_v[t])
                                        if store_bitset else None))
                scores_v = scores_v.at[:, t % K].add(
                    jnp.asarray(init_booster.leaf_value[t])[leaf_v]
                    * init_booster.tree_weight[t])
    best_metric = -np.inf if larger_better else np.inf
    best_iter = -1
    rounds_no_improve = 0
    if _resume_meta is not None:
        # restore the host-side loop state the snapshot carried: the PRNG
        # (feature/bagging/dart draws), early-stopping scalars, and evals
        rng.bit_generator.state = _resume_meta["rng_state"]
        best_metric = float(_resume_meta["best_metric"])
        best_iter = int(_resume_meta["best_iter"])
        rounds_no_improve = int(_resume_meta["rounds_no_improve"])
        evals[:] = [dict(e) for e in _resume_meta.get("evals", [])]

    feat_mask_full = jnp.ones((F,), bool)
    hist_mask_full = jnp.ones((n,), bool) if not shard_rows else jnp.asarray(w > 0)

    # Fused per-iteration step (single-program path): objective + GOSS + K
    # tree grows + score updates in ONE jitted XLA program — eager per-op
    # dispatch dominated the loop before fusion.
    grow_fn = None if shard_rows else _make_grower(p, F, B,
                                                   backend=hist_backend)
    shrink_const = 1.0 if p.boosting_type == "rf" else p.learning_rate
    is_goss = p.boosting_type == "goss"
    a_n = int(p.top_rate * n) if is_goss else 0
    b_n = int(p.other_rate * n) if is_goss else 0

    def _iter_body(scores, y_d, w_d, binned_d, base_mask, feat_mask_d, edges_d,
                   grad_scale, new_w, key, g_pre, h_pre, use_pre):
        with jax.named_scope(PHASE_GRAD):
            if use_pre:
                g, h = g_pre, h_pre
            else:
                g, h = objective(scores / grad_scale, y_d, w_d)
            hist_mask = base_mask
            if is_goss and not use_pre:
                absg = jnp.abs(g).sum(axis=1)
                order = jnp.argsort(-absg)
                top_idx = order[:a_n]
                rest = order[a_n:]
                perm = jax.random.permutation(key, rest.shape[0])
                small_idx = rest[perm[:b_n]]
                mask = jnp.zeros((n,), bool).at[top_idx].set(True) \
                    .at[small_idx].set(True)
                amp = (1.0 - p.top_rate) / max(p.other_rate, 1e-12)
                wamp = jnp.ones((n,)).at[small_idx].set(amp)
                hist_mask = hist_mask & mask
                g, h = g * wamp[:, None], h * wamp[:, None]
        tree_out = []
        for c in range(K):
            lch, rch, sf, th, tb, sg, iv, ic, lv, lc, cbs, leaf = grow_fn(
                binned_d, g[:, c], h[:, c], hist_mask, feat_mask_d, edges_d)
            with jax.named_scope(PHASE_UPDATE):
                lv_s = lv * shrink_const
                scores = scores.at[:, c].add(lv_s[leaf] * new_w)
            tree_out.append((lch, rch, sf, th, tb, sg, iv, ic, lv_s, lc, cbs))
        return scores, tree_out

    # scores is donated: each iteration consumes the previous score buffer
    # in place instead of allocating a fresh (n, K) f32 per dispatch.  The
    # use_pre=False variant binds g_pre/h_pre statically to None so the
    # donated scores buffer is never also passed as another (aliased) arg.
    _iter_jit = {} if shard_rows else {
        False: _cached(("iter", sig, F, K, n, False),
                       lambda: instrumented_jit(
                           partial(_iter_body, g_pre=None,
                                   h_pre=None, use_pre=False),
                           donate_argnums=(0,), name="lightgbm.iter")),
        True: _cached(("iter", sig, F, K, n, True),
                      lambda: instrumented_jit(
                          partial(_iter_body, use_pre=True),
                          donate_argnums=(0,), name="lightgbm.iter_pre"))}

    import jax.random as jrandom
    def _scoped_objective(scores, y_d, w_d):
        with jax.named_scope(PHASE_GRAD):
            return objective(scores, y_d, w_d)

    jit_objective = instrumented_jit(_scoped_objective,
                                     name="lightgbm.objective") \
        if objective is not None else None
    start_iter = len(tree_weights) // K

    # ---- scan-chunked multi-iteration path: CH boosting iterations per
    # device dispatch, amortizing the per-dispatch host gap.  Default ON for
    # the TPU; whether 4 is the right chunk is an open chip question
    # (ROADMAP S1/S3).  CPU keeps CH=1: scan compile cost dominates there.
    # MMLSPARK_TPU_GBDT_CHUNK overrides either way.
    _ch_env = __import__("os").environ.get("MMLSPARK_TPU_GBDT_CHUNK")
    if _ch_env is not None:
        CH = max(1, int(_ch_env))
    else:
        CH = 4 if platform() != "cpu" else 1
    chunk_ok = (CH > 1 and not shard_rows and p.objective != "lambdarank"
                and not p.categorical_features  # valid-walk is numerical-only
                and p.boosting_type != "dart" and p.bagging_freq <= 1
                and p.num_iterations >= 2 * CH
                and n >= 50_000)  # small data: scan compile cost dominates

    def _build_multi():
        keep = max(1, int(round(p.feature_fraction * F)))
        bag_on = p.bagging_freq > 0 and p.bagging_fraction < 1.0
        ff_on = p.feature_fraction < 1.0
        rf_mode = p.boosting_type == "rf"

        # the data rides as ARGUMENTS: this program is cached across
        # train() calls by (params, shape), so anything closed over here
        # would be the FIRST call's data, baked in as a constant
        def body(data, carry, key):
            binned, y_dev, w_dev, edges = data
            scores_c, t = carry
            with jax.named_scope(PHASE_GRAD):
                kf, kb, kg = jrandom.split(key, 3)
                feat_mask = jnp.ones((F,), bool)
                if ff_on:
                    sel = jrandom.choice(kf, F, (keep,), replace=False)
                    feat_mask = jnp.zeros((F,), bool).at[sel].set(True)
                base_mask = jnp.ones((n,), bool)
                if bag_on:
                    base_mask = jrandom.uniform(kb, (n,)) < p.bagging_fraction
                grad_scale = jnp.maximum(1.0, jnp.floor(t / K)) \
                    if rf_mode else 1.0
                g, h = objective(scores_c / grad_scale, y_dev, w_dev)
                hist_mask = base_mask
                if is_goss:
                    absg = jnp.abs(g).sum(axis=1)
                    order = jnp.argsort(-absg)
                    top_idx = order[:a_n]
                    rest = order[a_n:]
                    perm = jrandom.permutation(kg, rest.shape[0])
                    small_idx = rest[perm[:b_n]]
                    mask = jnp.zeros((n,), bool).at[top_idx].set(True) \
                        .at[small_idx].set(True)
                    amp = (1.0 - p.top_rate) / max(p.other_rate, 1e-12)
                    wamp = jnp.ones((n,)).at[small_idx].set(amp)
                    hist_mask = hist_mask & mask
                    g, h = g * wamp[:, None], h * wamp[:, None]
            outs = []
            for c in range(K):
                # chunked path excludes categoricals, so the bitset is a dummy
                lch, rch, sf, th, tb, sg, iv, ic, lv, lc, _cbs, leaf = grow_fn(
                    binned, g[:, c], h[:, c], hist_mask, feat_mask, edges)
                with jax.named_scope(PHASE_UPDATE):
                    lv_s = lv * shrink_const
                    scores_c = scores_c.at[:, c].add(lv_s[leaf])
                outs.append((lch, rch, sf, th, tb, sg, iv, ic, lv_s, lc))
            with jax.named_scope(PHASE_UPDATE):
                stacked = tuple(jnp.stack([o[j] for o in outs])
                                for j in range(10))
            return (scores_c, t + K), stacked

        def multi(scores_c, t0, keys, binned, y_dev, w_dev, edges):
            (scores_c, t), stacked = jax.lax.scan(
                partial(body, (binned, y_dev, w_dev, edges)),
                (scores_c, t0), keys)
            return scores_c, stacked

        return instrumented_jit(multi, donate_argnums=(0,),
                                name="lightgbm.multi_iter")

    multi_iter = _cached(("multi", sig, F, K, n, CH), _build_multi) if chunk_ok else None

    def _build_valid_update():
        def upd(scores_v_c, binned_v_c, sf_all, tb_all, lv_all, lch_all,
                rch_all):
            CK = sf_all.shape[0] * sf_all.shape[1]
            sf_f = sf_all.reshape(CK, -1)
            tb_f = tb_all.reshape(CK, -1)
            lv_f = lv_all.reshape(CK, -1)
            lch_f = lch_all.reshape(CK, -1)
            rch_f = rch_all.reshape(CK, -1)
            nv = binned_v_c.shape[0]

            def walk_one(sf_t, tb_t, lc_t, rc_t):
                node = jnp.zeros((nv,), jnp.int32)
                for _ in range(D):
                    j = jnp.maximum(node, 0)
                    f = sf_t[j]
                    tt = tb_t[j]
                    row_bin = binned_v_c[jnp.arange(nv),
                                         jnp.maximum(f, 0)].astype(jnp.int32)
                    go_right = (f >= 0) & (row_bin > tt)
                    child = jnp.where(go_right, rc_t[j], lc_t[j])
                    node = jnp.where(node >= 0, child, node)
                return ~node

            leaves = jax.vmap(walk_one)(sf_f, tb_f, lch_f, rch_f)   # (CK, nv)
            vals = jnp.take_along_axis(lv_f, leaves, axis=1)        # (CK, nv)
            for c in range(K):
                scores_v_c = scores_v_c.at[:, c].add(vals[c::K].sum(axis=0))
            return scores_v_c

        return instrumented_jit(upd, name="lightgbm.valid_update")

    valid_chunk_update = _cached(("validupd", D, K), _build_valid_update)

    it = start_iter
    bag_mask = None  # sampled lazily on the first bagging-eligible iteration
    if _resume_bag is not None:
        # stored packed bits cover the SNAPSHOT's padded width; re-pad to
        # this run's (the real rows [0, n_data) are identical, and padded
        # rows never enter a histogram regardless of their bag bit)
        _bits = np.unpackbits(_resume_bag)
        if _bits.size < n:
            _bits = np.pad(_bits, (0, n - _bits.size))
        bag_mask = jnp.asarray(_bits[:n].astype(bool))
    lambda_fn = None  # built on first lambdarank iteration, reused after
    _run_iter0 = start_iter
    _done_before = 0
    if _resume_meta is not None:
        _done_before = int(_resume_meta["iteration"])
        if _resume_meta.get("finished") and p.num_iterations <= int(
                _resume_meta.get("num_iterations", _done_before)):
            # the snapshot IS the finished run: skip the loop and return
            # its booster; a LARGER num_iterations target keeps training
            _done_before = p.num_iterations
    end_iter = start_iter + max(0, p.num_iterations - _done_before)
    _preempted = False
    _last_ckpt_iter = start_iter
    _trees_at_loop_start = len(tree_weights)

    def _save_ckpt_train(finished: bool, block: bool = False) -> None:
        # snapshot = list copies of DEVICE array refs (immutable; the tree
        # outputs are never donated) — np.asarray/stack/serialize/publish
        # all run on the manager's writer thread, so the boosting loop
        # never waits on the device fetch or the disk.  Completed-
        # iteration accounting derives from the TREE COUNT (one shared
        # convention with train_streamed): loop counters disagree with
        # completed work at early-stop breaks and mid-chunk boundaries.
        done = len(tree_weights) // K - _n_user_init_trees // K
        meta = _booster_ckpt_meta(done, _n_user_init_trees, rng,
                                  best_metric, best_iter, rounds_no_improve,
                                  evals, init_score, _ckpt_fingerprint,
                                  finished, p.num_iterations, "booster_v1",
                                  topology=_cur_topology)
        _mgr.save(done, _booster_ckpt_arrays(trees, tree_weights, bag_mask),
                  meta, block=block)

    # ---- live training monitor (ISSUE 19): opt-in heartbeat + stall
    # watchdog + HTTP sidecar; ticks ride the callbacks seam the loop
    # already invokes, so monitoring adds no new iteration hook
    _watch = _wsrv = None
    if monitor_port is not None or monitor_stall_timeout_s is not None:
        from ..observability.trainwatch import start_training_monitor
        _watch, _wsrv = start_training_monitor(
            "lightgbm.train", total_steps=p.num_iterations,
            rows_per_step=n, monitor_port=monitor_port,
            stall_timeout_s=monitor_stall_timeout_s,
            driver="lightgbm.train")
        _watch.set_phase("boosting")

        def _watch_cb(i, ev, _w=_watch):
            # the eval entry (when present) carries {metric_name: value,
            # "iteration": it} — feed the metric value to the loss tail
            val = None
            if isinstance(ev, dict):
                for k, v in ev.items():
                    if k != "iteration" and isinstance(v, (int, float)):
                        val = float(v)
                        break
            _w.tick(step=i + 1, loss=val)

        callbacks = list(callbacks or []) + [_watch_cb]

    _scope = preemption_scope() if _mgr is not None \
        else contextlib.nullcontext(PreemptionToken())
    with contextlib.ExitStack() as _stack:
      if _wsrv is not None:
          _stack.callback(_wsrv.stop)
      if _watch is not None:
          _stack.callback(_watch.close)
      _token = _stack.enter_context(_scope)
      if _watch is not None:
          _watch.set_preemption_token(_token)
      while it < end_iter:
        if _token.requested:
            # preempted: final checkpoint at this iteration boundary, then
            # a clean partial return the caller can resume from
            _save_ckpt_train(finished=False, block=True)
            _preempted = True
            break
        if _mgr is not None and checkpoint_every > 0 \
                and it - _last_ckpt_iter >= checkpoint_every:
            _save_ckpt_train(finished=False)
            _last_ckpt_iter = it
        if multi_iter is not None and end_iter - it >= CH:
            keys = jnp.stack([jrandom.PRNGKey(p.seed * 1000003 + it + j)
                              for j in range(CH)])
            _t_grow = time.perf_counter()
            with ambient_phase("lightgbm.histogram"):
                scores, stacked = multi_iter(scores,
                                             jnp.float32(len(tree_weights)),
                                             keys, binned, y_dev, w_dev,
                                             edges)
            # CH fused iterations per dispatch: book the per-iteration share
            # CH times so histogram counts stay 1:1 with boosting iterations
            _observe_phase("histogram_split_update",
                           (time.perf_counter() - _t_grow) / CH, times=CH)
            for ci in range(CH):
                for c in range(K):
                    for k_name, arr in zip(_TREE_KEYS, stacked):
                        trees[k_name].append(arr[ci, c])
                    tree_weights.append(1.0)
            if has_valid:
                _t_eval = time.perf_counter()
                with ambient_phase("lightgbm.eval"):
                    scores_v = valid_chunk_update(scores_v, binned_v,
                                                  stacked[2], stacked[4],
                                                  stacked[8], stacked[0],
                                                  stacked[1])
                    raw_v = np.asarray(scores_v, np.float64)
                    m = metric_fn(yv, raw_v)
                _observe_phase("eval", time.perf_counter() - _t_eval)
                evals.append({metric_name: m, "iteration": it + CH - 1})
                improved = m > best_metric if larger_better else m < best_metric
                if improved:
                    best_metric, best_iter, rounds_no_improve = m, it + CH - 1, 0
                else:
                    rounds_no_improve += CH
                if p.early_stopping_round > 0 and \
                        rounds_no_improve >= p.early_stopping_round:
                    break
            if callbacks:
                for cb in callbacks:
                    cb(it + CH - 1, evals[-1] if evals else None)
            it += CH
            continue

        # ---- host-side per-iteration randomness
        feat_mask = feat_mask_full
        if p.feature_fraction < 1.0:
            keep = max(1, int(round(p.feature_fraction * F)))
            sel = rng.choice(F, size=keep, replace=False)
            feat_mask = jnp.zeros((F,), bool).at[jnp.asarray(sel)].set(True)
        base_mask = hist_mask_full
        if p.boosting_type != "goss" and p.bagging_freq > 0 and p.bagging_fraction < 1.0:
            # resample on schedule-aligned iterations AND on the first
            # iteration of this call (a warm start may begin off-schedule,
            # in which case bag_mask would otherwise be unbound)
            if it % p.bagging_freq == 0 or bag_mask is None:
                # draw over the UNPADDED rows (padded tail stays out of
                # the bag): the PRNG stream — and so every later draw —
                # is then independent of the mesh width, which elastic
                # resume's cross-width bit-identity rides on (ISSUE 14)
                _draw = rng.random(n_data) < p.bagging_fraction
                bag_mask = jnp.asarray(np.pad(_draw, (0, n - n_data)))
            base_mask = hist_mask_full & bag_mask

        # ---- gradients precomputed for lambdarank / dart
        _t_grad = time.perf_counter()
        g_pre = h_pre = None
        dropped: List[int] = []
        if p.objective == "lambdarank":
            if group_ptr is None:
                raise ValueError("lambdarank requires group_ptr")
            if lambda_fn is None:  # packing gathers built once, then the
                lambda_fn = make_lambdarank_grad_fn(y, group_ptr, p.sigmoid)
            g_pre, h_pre = lambda_fn(scores)  # stays on device every iter
        elif p.boosting_type == "dart" and tree_weights and rng.random() >= p.skip_drop:
            k_drop = min(p.max_drop, max(1, int(round(p.drop_rate * len(tree_weights)))))
            dropped = sorted(rng.choice(len(tree_weights), size=min(k_drop, len(tree_weights)),
                                        replace=False).tolist())
            drop_delta = jnp.zeros_like(scores)
            for t in dropped:
                leaf = walker(binned, trees["split_feature"][t],
                              trees["threshold_bin"][t],
                              trees["left_child"][t], trees["right_child"][t],
                              bitset=(trees["cat_bitset"][t]
                                      if store_bitset else None))
                drop_delta = drop_delta.at[:, t % K].add(
                    trees["leaf_value"][t][leaf] * tree_weights[t])
            g_pre, h_pre = jit_objective(scores - drop_delta, y_dev, w_dev)

        new_w = 1.0 / (1.0 + len(dropped)) if dropped else 1.0
        grad_scale = float(max(1, len(tree_weights) // K)) \
            if p.boosting_type == "rf" and tree_weights else 1.0
        key = jrandom.PRNGKey(p.seed * 1000003 + it)
        if g_pre is not None:  # lambdarank/dart gradients were built above
            _observe_phase("gradients", time.perf_counter() - _t_grad)

        _t_grow = time.perf_counter()
        if not shard_rows:
            use_pre = g_pre is not None
            with ambient_phase("lightgbm.histogram"):
                if use_pre:
                    scores, tree_out = _iter_jit[True](
                        scores, y_dev, w_dev, binned, base_mask, feat_mask,
                        edges, grad_scale, new_w, key, g_pre, h_pre)
                else:
                    scores, tree_out = _iter_jit[False](
                        scores, y_dev, w_dev, binned, base_mask, feat_mask,
                        edges, grad_scale, new_w, key)
            # one fused program: histogram build + split find + score update
            _observe_phase("histogram_split_update",
                           time.perf_counter() - _t_grow)
        else:
            # multi-chip path: explicit shard_map grower per class — the
            # only path where gradients / grow / update dispatch separately
            if g_pre is not None:
                g_eff, h_eff = g_pre, h_pre
            else:
                g_eff, h_eff = jit_objective(scores / grad_scale, y_dev, w_dev)
                _observe_phase("gradients", time.perf_counter() - _t_grow)
            shrink = 1.0 if p.boosting_type == "rf" else p.learning_rate
            tree_out = []
            for c in range(K):
                _t_c = time.perf_counter()
                with ambient_phase("lightgbm.histogram"):
                    (lch, rch, sf, th, tb, sg, iv, ic, lv, lc, cbs,
                     leaf_of_row) = grower(
                        binned, g_eff[:, c], h_eff[:, c], base_mask,
                        feat_mask, edges)
                _observe_phase("histogram_split", time.perf_counter() - _t_c)
                _t_u = time.perf_counter()
                lv_s = lv * shrink
                scores = scores.at[:, c].add(lv_s[leaf_of_row] * new_w)
                tree_out.append((lch, rch, sf, th, tb, sg, iv, ic, lv_s, lc,
                                 cbs))
                _observe_phase("update", time.perf_counter() - _t_u)

        for c, (lch, rch, sf, th, tb, sg, iv, ic, lv_s, lc, cbs) \
                in enumerate(tree_out):
            # keep tree arrays on device: every host fetch is a device
            # sync; one device_get happens after the loop
            vals = (lch, rch, sf, th, tb, sg, iv, ic, lv_s, lc) \
                + ((cbs,) if store_bitset else ())
            for k_name, v in zip(tree_keys, vals):
                trees[k_name].append(v)
            tree_weights.append(new_w)
            if has_valid:
                leaf_v = walker(binned_v, sf, tb, lch, rch,
                                bitset=cbs if store_bitset else None)
                scores_v = scores_v.at[:, c].add(lv_s[leaf_v] * new_w)

        # ---- dart renormalize dropped trees
        if p.boosting_type == "dart" and dropped:
            factor = len(dropped) / (1.0 + len(dropped))
            for t in dropped:
                # subtract the shrunken part from train/valid scores
                bs_t = trees["cat_bitset"][t] if store_bitset else None
                leaf = walker(binned, trees["split_feature"][t],
                              trees["threshold_bin"][t],
                              trees["left_child"][t], trees["right_child"][t],
                              bitset=bs_t)
                delta = trees["leaf_value"][t][leaf] * tree_weights[t] * (factor - 1.0)
                scores = scores.at[:, t % K].add(delta)
                if has_valid:
                    leaf_v = walker(binned_v, trees["split_feature"][t],
                                    trees["threshold_bin"][t],
                                    trees["left_child"][t],
                                    trees["right_child"][t], bitset=bs_t)
                    delta_v = trees["leaf_value"][t][leaf_v] * tree_weights[t] * (factor - 1.0)
                    scores_v = scores_v.at[:, t % K].add(delta_v)
                tree_weights[t] *= factor

        # ---- eval / early stopping
        if has_valid:
            _t_eval = time.perf_counter()
            with ambient_phase("lightgbm.eval"):
                raw_v = np.asarray(scores_v, np.float64)
                m = metric_fn(yv, raw_v)
            _observe_phase("eval", time.perf_counter() - _t_eval)
            evals.append({metric_name: m, "iteration": it})
            improved = m > best_metric if larger_better else m < best_metric
            if improved:
                best_metric, best_iter, rounds_no_improve = m, it, 0
            else:
                rounds_no_improve += 1
            if p.early_stopping_round > 0 and rounds_no_improve >= p.early_stopping_round:
                break
        if callbacks:
            for cb in callbacks:
                cb(it, evals[-1] if evals else None)
        it += 1

    if _mgr is not None:
        if not _preempted and (len(tree_weights) > _trees_at_loop_start
                              or _resume_meta is None):
            # terminal snapshot (covers early stopping too): resume of a
            # finished run restores the final booster without retraining;
            # a finished-run restore that grew nothing skips the re-save
            _save_ckpt_train(finished=True, block=True)
        _mgr.close()

    trees_np = jax.device_get({k: v for k, v in trees.items()})  # one transfer
    lch_np = np.stack(trees_np["left_child"])
    rch_np = np.stack(trees_np["right_child"])
    if p.growth == "leaf":
        # tight walk bound: leaf-wise trees are usually far shallower than
        # the worst-case num_leaves - 1 chain (this also covers deeper
        # warm-start trees, which are in lch_np/rch_np too)
        from ..models.gbdt import children_depth_bound
        D = children_depth_bound(lch_np, rch_np)
    elif init_booster is not None:
        # level-wise continuation must keep a bound that resolves the
        # warm-start trees, which may be deeper than this run's depth
        D = max(D, init_booster.max_depth)
    cat_bitset = None
    if store_bitset:
        cat_bitset = np.stack([np.asarray(a, bool)
                               for a in trees_np["cat_bitset"]])
    booster = GBDTBooster(
        np.stack(trees_np["split_feature"]), np.stack(trees_np["threshold"]),
        np.stack(trees_np["threshold_bin"]), np.stack(trees_np["split_gain"]),
        np.stack(trees_np["internal_value"]), np.stack(trees_np["internal_count"]),
        np.stack(trees_np["leaf_value"]), np.stack(trees_np["leaf_count"]),
        np.asarray(tree_weights, np.float32),
        left_child=lch_np, right_child=rch_np,
        max_depth=D, num_features=F, objective=p.objective, num_class=K,
        init_score=init_score, average_output=(p.boosting_type == "rf"),
        feature_names=feature_names, best_iteration=best_iter, sigmoid=p.sigmoid,
        categorical_features=list(p.categorical_features or []),
        cat_bitset=cat_bitset)
    for k, v in sorted(_phase_totals.items()):
        _train_span.set_attribute(f"phase.{k}_s", round(v, 6))
    _train_span.set_attribute("rows", n)
    _train_span.set_attribute("features", F)
    _train_span.set_attribute("iterations", len(tree_weights) // K)
    _train_span.set_attribute("growth", p.growth)
    # the path that ran, for whoever reads the span (chip_smoke.py does)
    _train_span.set_attribute("hist_backend", hist_backend)
    _train_span.set_attribute("quantized", bool(p.use_quantized_grad))
    _train_span.set_attribute("chunk", CH if multi_iter is not None else 1)
    _extras = None
    if _mgr is not None:
        _extras = {"preempted": float(_preempted),
                   "resumed_from_iteration":
                       float(_resume_meta["iteration"])
                       if _resume_meta is not None else -1.0,
                   "checkpoint_saves": float(_mgr.saves_ok),
                   "resharded": float(_resharded)}
        for k, v in _extras.items():
            _train_span.set_attribute(f"ckpt.{k}", v)
    export_span(_train_span)
    return TrainResult(booster=booster, evals=evals, bin_mapper=mapper,
                       extras=_extras)


# ---------------------------------------------------------------------------
# out-of-core streamed training (ISSUE 7): host-RAM tiles -> device HBM
# ---------------------------------------------------------------------------

def _check_quant_tile_bound(use_quant: bool, quant_bins: int,
                            total_rows: int) -> None:
    """Tile-accumulation twin of ``_check_quant_psum_bound``: each per-tile
    build guards int32 overflow against its OWN tile's rows, but the driver
    accumulates decoded partials across every tile — a root-level cell can
    hold the full dataset's sums, so the guard must see the total."""
    if not use_quant:
        return
    qh_cap = max(1, quant_bins - 1)
    if int(total_rows) * qh_cap >= (1 << 31):
        raise ValueError(
            "quantized histograms overflow int32 when accumulated across "
            f"tiles above {(1 << 31) // qh_cap} total rows at {quant_bins} "
            "quantization bins — lower num_grad_quant_bins or disable "
            "use_quantized_grad")


#: the streamed paths' array-of-nodes tree surface (booster column order)
_STREAM_TREE_KEYS = ("left_child", "right_child", "split_feature",
                     "threshold", "threshold_bin", "split_gain",
                     "internal_value", "internal_count", "leaf_value",
                     "leaf_count")


def _quant_mix(g_host: np.ndarray, h_host: np.ndarray) -> np.int32:
    """Per-iteration quantization key mix for the streamed driver: an
    exact INTEGER fold of the bitcast |grad|/hess magnitudes over the
    whole host row space.  Integer adds are associative and the host
    arrays are tile-independent, so the mix — and every row's stochastic
    rounding — survives a resume onto a different tile width bit-for-bit
    (the tile-level twin of the sharded grower's psum'd mix)."""
    gi = int(np.abs(g_host).view(np.int32).astype(np.int64).sum())
    hi = int(h_host.view(np.int32).astype(np.int64).sum())
    total = (gi + 3 * hi) & 0xFFFFFFFF
    if total >= 1 << 31:
        total -= 1 << 32
    return np.int32(total)


def _booster_ckpt_arrays(trees: Dict[str, list], tree_weights: list,
                         bag_mask) -> Callable[[], Dict[str, np.ndarray]]:
    """Snapshot-arrays callable shared by ``train`` and ``train_streamed``
    (one copy so the two drivers' checkpoint formats cannot drift).  The
    training thread pays only list copies; ``np.asarray``/``np.stack``/
    ``np.packbits`` — including any device-to-host fetches for device-
    resident trees or bagging masks — run on the manager's writer thread.
    Tree arrays and the bag mask are immutable once captured (the loop
    REBINDS them rather than mutating), so the deferred reads are safe."""
    tl = {k: list(v) for k, v in trees.items()}
    tw = list(tree_weights)

    def _arrays(tl=tl, tw=tw, bm=bag_mask):
        out = {k: np.stack([np.asarray(a) for a in v])
               for k, v in tl.items()}
        out["tree_weight"] = np.asarray(tw, np.float32)
        if bm is not None:
            out["bag_mask"] = np.packbits(np.asarray(bm, bool))
        return out

    return _arrays


def _booster_ckpt_meta(completed_iter: int, n_init_trees: int, rng,
                       best_metric, best_iter: int, rounds_no_improve: int,
                       evals: list, init_score: float, fingerprint: str,
                       finished: bool, num_iterations: int,
                       fmt: str, topology: Optional[Dict] = None) -> Dict:
    """Snapshot meta shared by both drivers.  ``completed_iter`` is the
    ONE convention both must use: boosting iterations completed beyond the
    user's warm-start trees, derived from the tree count (robust to early
    stopping and the fused multi-iteration chunk path, where loop counters
    and completed work can disagree at the break).  ``topology`` is the
    recorded-but-not-identity stanza (ISSUE 14): a resume onto a changed
    mesh width / tile geometry diffs it instead of rejecting it."""
    meta = {"iteration": int(completed_iter),
            "n_init_trees": int(n_init_trees),
            "rng_state": rng.bit_generator.state,
            "best_metric": best_metric, "best_iter": int(best_iter),
            "rounds_no_improve": int(rounds_no_improve),
            "evals": [dict(e) for e in evals],
            "init_score": float(init_score),
            "fingerprint": fingerprint, "finished": bool(finished),
            "num_iterations": int(num_iterations), "format": fmt}
    if topology is not None:
        meta["topology"] = topology
    return meta


_CKPT_FINGERPRINT_MISMATCH = (
    "checkpoint_dir holds a snapshot for different data or params "
    "(fingerprint mismatch) — point checkpoint_dir at a fresh directory, "
    "or pass resume='never' (docs/RESILIENCE.md: training fault tolerance)")


def _np_walk_tree(binned: np.ndarray, sf: np.ndarray, tb: np.ndarray,
                  lch: np.ndarray, rch: np.ndarray,
                  depth_bound: int) -> np.ndarray:
    """Host twin of ``make_binned_walker`` for numerical splits: per-row
    leaf index of ONE tree over host-resident binned data.  Integer
    compares and gathers only, so the leaf assignment is exactly the one
    the device walker (and the streamed router) produces — which is what
    lets resume replay reconstruct training scores bit-for-bit without
    ever putting the full binned matrix on device."""
    n = binned.shape[0]
    node = np.zeros((n,), np.int64)
    rows = np.arange(n)
    sf = np.asarray(sf, np.int64)
    tb = np.asarray(tb, np.int64)
    lch = np.asarray(lch, np.int64)
    rch = np.asarray(rch, np.int64)
    for _ in range(max(1, int(depth_bound))):
        j = np.maximum(node, 0)
        f = sf[j]
        go_right = (f >= 0) & (binned[rows, np.maximum(f, 0)].astype(np.int64)
                               > tb[j])
        child = np.where(go_right, rch[j], lch[j])
        node = np.where(node >= 0, child, node)
    return ~node


def _np_leaf_output(G, H, l1: float, l2: float, max_delta: float):
    """Host-side twin of the growers' leaf_output (f32 in, f32 out).
    Empty nodes (G=H=0, l2=0) yield NaN exactly like the device version —
    callers mask them behind a count check, so the numpy warning is
    suppressed rather than papered over with a fake value."""
    with np.errstate(invalid="ignore", divide="ignore"):
        t = np.sign(G) * np.maximum(np.abs(G) - l1, 0.0)
        v = (-t / (H + l2)).astype(np.float32)
    if max_delta > 0:
        v = np.clip(v, -max_delta, max_delta)
    return v


def train_streamed(X, y: Optional[np.ndarray] = None, params: GBDTParams = None,
                   sample_weight: Optional[np.ndarray] = None,
                   valid: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                   tile_rows: Optional[int] = None,
                   memory_budget_bytes: Optional[int] = None,
                   feature_names: Optional[List[str]] = None,
                   init_booster: Optional[GBDTBooster] = None,
                   callbacks: Optional[List[Callable]] = None,
                   checkpoint_dir: Optional[str] = None,
                   checkpoint_every: int = 0,
                   checkpoint_keep_last: int = 3,
                   resume: str = "auto",
                   monitor_port: Optional[int] = None,
                   monitor_stall_timeout_s: Optional[float] = None
                   ) -> TrainResult:
    """Out-of-core boosting: the dataset lives in host RAM and streams
    through the device in fixed-shape tiles with double-buffered prefetch
    (Snap ML's host->HBM hierarchy, ``io.chunked``).  Nothing row-sized is
    ever resident on the device except the two live tiles, so the trainable
    dataset is bounded by host RAM, not HBM.

    Numerics contract (tested): bin edges come from a streaming quantile
    sketch (identical to the in-memory fit whenever the stream fits the
    sample budget); quantization scales come from a global max first pass
    over every tile, so each tile quantizes in IDENTICAL units and the
    per-tile int32 histogram partials accumulate bit-exactly to the
    monolithic build; split decisions therefore see the same integer sums
    either way.  The only divergence from ``train`` is the stochastic
    rounding noise (keyed per tile instead of per dataset), which is
    unbiased — end-to-end parity holds within the committed accuracy-gate
    precisions.

    Both grower families stream: ``growth="level"`` runs one accumulate ->
    decide -> route cycle per level (D passes over the tiles per tree);
    ``growth="leaf"`` rebuilds the split leaf's left child per step and
    derives the sibling by exact integer subtraction from a host-resident
    stored-histogram table (``num_leaves - 1`` passes per tree).

    ``X`` may be a raw ``(n, F)`` array or a prebuilt
    :class:`~mmlspark_tpu.io.chunked.ChunkedDataset` (then ``y``/``w`` ride
    its columns).  Tile size resolves from ``tile_rows`` /
    ``memory_budget_bytes`` / ``MMLSPARK_TPU_TILE_ROWS`` (see
    ``io.chunked.resolve_tile_rows``); prefetch overlap books into
    ``mmlspark_prefetch_wait_seconds`` / ``mmlspark_tile_compute_seconds``
    and is returned in ``TrainResult.extras``.

    Warm start: ``init_booster`` continues training from an existing
    single-output gbdt booster, matching ``train()`` — its trees replay on
    the host (exact integer walks + the same float32 score adds training
    performs), so continuation optimizes against the recorded scores.
    Binning must agree with the booster's (same dataset or same edge
    semantics, the ``train()`` contract).

    Fault tolerance (ISSUE 10): with ``checkpoint_dir`` set, the run
    snapshots its booster-so-far + iteration + host PRNG/bagging state
    atomically every ``checkpoint_every`` iterations (plus once at the
    end), serialization riding a background writer thread so device work
    never waits on disk; ``resume="auto"`` restores the newest VALID
    snapshot (a torn newest falls back to the previous one) and continues
    through the same replay machinery — the resumed run's booster is
    bit-identical to an uninterrupted one (the integer histogram path
    makes that exact; tested by the chaos harness).  SIGTERM/SIGINT
    during the loop requests one final checkpoint at the next iteration
    boundary and returns cleanly with ``extras["preempted"]`` set.
    ``resume="must"`` raises when no usable snapshot exists.

    Elastic resume (ISSUE 14): the snapshot's topology stanza records the
    tile geometry but is NOT identity — a resume may re-partition the row
    stream onto a different ``tile_rows``/``num_tiles`` (the change books
    ``mmlspark_reshard_total`` and sets ``extras["resharded"]``).  With
    quantized histograms the rounding noise is keyed per GLOBAL row, so
    the per-tile int32 partials accumulate to the same integers under any
    tiling and the resumed booster stays bit-identical to an
    uninterrupted run at either width (tested shrink and grow).

    Live monitoring (ISSUE 19): ``monitor_port`` (0 = ephemeral) serves
    ``GET /progress`` — step/ETA, rows/sec EWMA, loss tail, live tile
    overlap %, checkpoint age — plus ``/metrics`` and
    ``/debug/{dump,profile}`` for the duration of the loop; either monitor
    arg arms a stall watchdog whose ``train_stall`` flight dump captures
    the prefetch state a hung tile load leaves behind (see
    docs/OBSERVABILITY.md "Training plane").

    Not (yet) streamed: multiclass, lambdarank, dart/goss/rf, categorical
    features, and ``shard_rows`` (the multi-host composition — per-tile
    accumulation under ``collectives.histogram_psum(num_tiles=)`` — is
    exercised at the collective level; see docs/out_of_core.md).
    """
    import jax
    import jax.numpy as jnp
    from ..io.chunked import ChunkedDataset, TilePrefetcher, pad_tile
    from ..observability.compute import device_put as _obs_device_put
    from ..observability.tracing import (Span, ambient_phase, current_span,
                                         export_span)
    from ..ops import histogram as hist_ops

    if params is None:
        raise ValueError("params is required")
    p = params.resolve()
    if p.objective in ("lambdarank", "multiclass"):
        raise ValueError(f"streamed training does not support objective="
                         f"{p.objective!r} yet (see docs/out_of_core.md)")
    if p.boosting_type != "gbdt":
        raise ValueError("streamed training supports boosting_type='gbdt' "
                         f"only (got {p.boosting_type!r})")
    if p.categorical_features:
        raise ValueError("streamed training does not support categorical "
                         "features yet (see docs/out_of_core.md)")

    # ---- dataset geometry
    if isinstance(X, ChunkedDataset):
        cd = X
        if tile_rows is not None or memory_budget_bytes is not None:
            raise ValueError("tile sizing belongs to the ChunkedDataset "
                             "when one is passed directly")
        y = cd.columns.get("y") if y is None else np.asarray(y, np.float32)
        w = cd.columns.get("w")
        if w is not None and sample_weight is not None:
            raise ValueError("sample weights belong to the ChunkedDataset "
                             "('w' column) when one is passed directly")
    else:
        cd = ChunkedDataset(np.asarray(X, np.float32), tile_rows=tile_rows,
                            memory_budget_bytes=memory_budget_bytes)
        w = None
    if y is None:
        raise ValueError("labels are required (y= or a 'y' dataset column)")
    y = np.asarray(y, np.float32)
    n, F = cd.n_rows, cd.num_features
    T = cd.tile_rows
    if w is None:
        w = np.ones(n, np.float32) if sample_weight is None \
            else np.asarray(sample_weight, np.float32)
    if len(y) != n or len(w) != n:
        raise ValueError("X, y and sample_weight row counts disagree")
    if p.objective in ("poisson", "tweedie") and (y < 0).any():
        raise ValueError(f"objective {p.objective!r} requires non-negative "
                         "labels")
    if p.objective == "gamma" and (y <= 0).any():
        raise ValueError("objective 'gamma' requires strictly positive "
                         "labels")
    if init_booster is not None:
        # continuation guards, same raise-with-pointer shape as the other
        # streamed rejects: the streamed path is single-output numerical
        # gbdt, so only boosters of that shape can continue here
        if init_booster.num_class != 1 or init_booster.objective == "multiclass":
            raise ValueError(
                "streamed continuation supports single-output boosters only "
                f"(init_booster.num_class={init_booster.num_class}); use "
                "train() for multiclass continuation (docs/out_of_core.md)")
        if bool(getattr(init_booster, "average_output", False)):
            raise ValueError(
                "streamed training does not support rf-averaged boosters "
                "(boosting_type='rf' is not streamed; docs/out_of_core.md)")
        if getattr(init_booster, "categorical_features", None) \
                or getattr(init_booster, "cat_bitset", None) is not None:
            raise ValueError(
                "streamed training does not support categorical features "
                "yet, so a categorical booster cannot continue here "
                "(docs/out_of_core.md)")
        if int(init_booster.num_features) != F:
            raise ValueError(
                f"init_booster was trained on {init_booster.num_features} "
                f"features, dataset has {F}")

    # ---- backend / quantization resolution (same contract as train())
    hist_backend, _uq = _resolve_hist_path(p)
    p = dataclasses.replace(p, use_quantized_grad=_uq)
    use_quant = p.use_quantized_grad
    qb = p.num_grad_quant_bins
    qg_cap = max(1, qb // 2)
    qh_cap = max(1, qb - 1)
    _check_quant_tile_bound(use_quant, qb, n)
    sig = _params_sig(p) + (hist_backend,)

    _parent = current_span()
    _span = Span("lightgbm.train_streamed",
                 trace_id=_parent.trace_id if _parent else None,
                 parent_id=_parent.span_id if _parent else None)

    # ---- streamed binning: sketch pass (host), then host uint8 tiles
    def _tile_chunks():
        for i in range(cd.num_tiles):
            lo, hi = cd.tile_slice(i)
            yield cd.X[lo:hi]

    with ambient_phase("ooc.binning"):
        mapper = BinMapper(p.max_bin).fit_streaming(_tile_chunks())
        B = mapper.num_bins
        binned_h = np.empty((n, F), np.uint8)
        for i in range(cd.num_tiles):
            lo, hi = cd.tile_slice(i)
            binned_h[lo:hi] = mapper.transform(cd.X[lo:hi])
    edges_np = mapper.edges
    edge_ok = np.concatenate(
        [np.isfinite(edges_np), np.zeros((F, 1), bool)], axis=1)
    edge_ok_dev = jnp.asarray(edge_ok)

    l1, l2 = p.lambda_l1, p.lambda_l2
    min_data = float(p.min_data_in_leaf)
    min_hess = p.min_sum_hessian_in_leaf
    min_gain = p.min_gain_to_split
    max_delta = p.max_delta_step
    lr = p.learning_rate
    objective = make_objective(p)
    D = p.depth_bound
    rng = np.random.default_rng(p.seed)

    def thresh(G):
        return jnp.sign(G) * jnp.maximum(jnp.abs(G) - l1, 0.0)

    def leaf_score(G, H):
        return thresh(G) ** 2 / (H + l2)

    def dehist(h_, gsc, hsc):
        if not use_quant:
            return h_
        return hist_ops.dequantize_histogram(h_, gsc, hsc)

    # ---- jitted per-tile kernels (ONE signature across all tiles: the
    # static tile shape is the point of ChunkedDataset)
    def _build_grad():
        def grad_tile(scores_t, y_t, w_t):
            g, h = objective(scores_t[:, None], y_t, w_t)
            return (g[:, 0], h[:, 0],
                    jnp.max(jnp.abs(g)), jnp.max(h))
        return instrumented_jit(grad_tile, name="lightgbm.ooc_grad")

    grad_fn = _cached(("ooc_grad", sig, T), _build_grad)

    def _build_accum():
        def accum(acc, b_t, g_t, h_t, node_t, ids_t, mixv, gsc, hsc):
            nodes_d = acc.shape[0]          # static at trace time
            if use_quant:
                # noise keyed per GLOBAL row id + one per-iteration mix
                # (elastic resume, ISSUE 14): a row quantizes identically
                # under ANY tile width, so per-tile int32 partials
                # accumulate to the same integers after a re-tiled resume
                qg, qh, _, _ = hist_ops.quantize_gradients(
                    g_t, h_t, qb, seed=p.seed, g_scale=gsc, h_scale=hsc,
                    row_ids=ids_t, mix=mixv)
                part = hist_ops.build_quantized(
                    b_t, qg, qh, node_t, nodes_d, B, quant_bins=qb,
                    backend=hist_backend, node_rows_bound=T)
            else:
                part = hist_ops.build(b_t, g_t, h_t, node_t, nodes_d, B,
                                      backend=hist_backend)
            return acc + part
        # level growth legitimately compiles one signature per level (the
        # acc node axis doubles: nodes_d = 1..2^(D-1)), so the storm
        # threshold scales with depth — the default 8 would book a false
        # recompile-storm on any healthy max_depth>=8 run
        return instrumented_jit(accum, donate_argnums=(0,),
                                name="lightgbm.ooc_tile_hist",
                                storm_signatures=D + 8)

    accum_fn = _cached(("ooc_accum", sig, F, B, T), _build_accum)

    def _build_decide():
        def decide(acc, gsc, hsc, fmask, eok):
            hist = dehist(acc, gsc, hsc)              # (nodes, F, B, 3)
            nodes_d = hist.shape[0]
            cum = jnp.cumsum(hist, axis=2)
            tot = cum[:, :1, -1, :]                   # (nodes, 1, 3)
            GL, HL, CL = cum[..., 0], cum[..., 1], cum[..., 2]
            Gp, Hp, Cp = tot[..., 0], tot[..., 1], tot[..., 2]
            GR, HR, CR = (Gp[:, :, None] - GL, Hp[:, :, None] - HL,
                          Cp[:, :, None] - CL)
            gain = (leaf_score(GL, HL) + leaf_score(GR, HR)
                    - leaf_score(Gp, Hp)[:, :, None])
            valid = ((CL >= min_data) & (CR >= min_data)
                     & (HL >= min_hess) & (HR >= min_hess)
                     & fmask[None, :, None] & eok[None])
            gain = jnp.where(valid, gain, -jnp.inf)
            flat = gain.reshape(nodes_d, F * B)
            best = jnp.argmax(flat, axis=1)
            best_gain = jnp.take_along_axis(flat, best[:, None],
                                            axis=1)[:, 0]
            bf = (best // B).astype(jnp.int32)
            bb = (best % B).astype(jnp.int32)
            do = best_gain > min_gain
            pick = jnp.stack([GL, HL, CL], axis=-1)
            left = pick[jnp.arange(nodes_d), bf, bb, :]
            tot3 = jnp.stack([Gp[:, 0], Hp[:, 0], Cp[:, 0]], axis=-1)
            left_stats = jnp.where(do[:, None], left, tot3)
            return bf, bb, do, best_gain, left_stats, tot3 - left_stats, tot3
        # one signature per level, like the accumulator above
        return instrumented_jit(decide, name="lightgbm.ooc_level_decide",
                                storm_signatures=D + 8)

    decide_fn = _cached(("ooc_decide", sig, F, B), _build_decide)

    def _build_leaf_best():
        def leaf_best(hist_f3, gsc, hsc, fmask, depth_ok, eok):
            hist = dehist(hist_f3, gsc, hsc)          # (F, B, 3)
            cum = jnp.cumsum(hist, axis=1)
            tot = cum[0, -1, :]
            GL, HL, CL = cum[..., 0], cum[..., 1], cum[..., 2]
            GR, HR, CR = tot[0] - GL, tot[1] - HL, tot[2] - CL
            gain = (leaf_score(GL, HL) + leaf_score(GR, HR)
                    - leaf_score(tot[0], tot[1]))
            valid = ((CL >= min_data) & (CR >= min_data)
                     & (HL >= min_hess) & (HR >= min_hess)
                     & fmask[:, None] & depth_ok & eok)
            gain = jnp.where(valid, gain, -jnp.inf)
            flat = gain.reshape(-1)
            best = jnp.argmax(flat)
            bf = (best // B).astype(jnp.int32)
            bb = (best % B).astype(jnp.int32)
            left = jnp.stack([GL, HL, CL], axis=-1)[bf, bb]
            return flat[best], bf, bb, left, tot
        return instrumented_jit(leaf_best, name="lightgbm.ooc_leaf_best")

    leaf_best_fn = _cached(("ooc_leaf_best", sig, F, B), _build_leaf_best)

    # ---- prefetch plumbing: payloads built AND placed on the worker
    # thread (routing for the next tile rides there too, overlapped with
    # the consumer's histogram dispatch on the current tile)
    OOC_SITE = "lightgbm.ooc_tile"
    stream_totals = {"wait_s": 0.0, "compute_s": 0.0, "tiles": 0.0}
    # the live prefetcher (one active pass at a time): /progress and the
    # train_stall flight dump read its snapshot() — a hung tile load shows
    # up as waiting=True with tiles_served frozen
    _live_pf: Dict[str, Optional[TilePrefetcher]] = {"pf": None}

    def _stream(make_tile):
        def load(i):
            # prefetch worker thread: attribute its samples to tile load,
            # distinct from the consumer's accumulate dispatch
            with ambient_phase("ooc.tile_load"):
                lo, hi = cd.tile_slice(i)
                host = make_tile(i, lo, hi)
                return (i, lo, hi, _obs_device_put(host, site=OOC_SITE))
        pf = TilePrefetcher(range(cd.num_tiles), load, site=OOC_SITE)
        _live_pf["pf"] = pf
        return pf

    def _finish_stream(pf):
        st = pf.overlap_stats()
        stream_totals["wait_s"] += st["wait_s"]
        stream_totals["compute_s"] += st["compute_s"]
        stream_totals["tiles"] += st["tiles"]

    def _prefetch_state() -> Dict[str, Any]:
        """Monitor-side view: cumulative overlap totals + the live pass."""
        busy = stream_totals["wait_s"] + stream_totals["compute_s"]
        d: Dict[str, Any] = {
            "wait_s": round(stream_totals["wait_s"], 6),
            "compute_s": round(stream_totals["compute_s"], 6),
            "tiles": stream_totals["tiles"],
            "overlap_pct": round(
                100.0 * stream_totals["compute_s"] / busy, 2)
            if busy > 0 else 100.0,
        }
        pf = _live_pf["pf"]
        if pf is not None:
            d["live"] = pf.snapshot()
        return d

    # ---- init score (same as train())
    init_score = 0.0
    if p.objective == "binary":
        pbar = float(np.clip(np.average(y, weights=w), 1e-6, 1 - 1e-6))
        init_score = math.log(pbar / (1 - pbar)) / p.sigmoid
    elif p.objective in ("regression", "huber"):
        init_score = float(np.average(y, weights=w))
    elif p.objective in ("poisson", "tweedie", "gamma"):
        init_score = float(np.log(max(np.average(y, weights=w), 1e-9)))
    elif p.objective == "regression_l1":
        init_score = float(np.median(y))
    scores_h = np.full((n,), init_score, np.float32)
    g_host = np.empty((n,), np.float32)
    h_host = np.empty((n,), np.float32)

    # ---- valid set (in-memory: the heldout set is driver-sized)
    metric_name = p.metric or default_metric(p.objective)
    metric_fn, larger_better = resolve_metric(metric_name, p)
    evals: List[Dict[str, float]] = []
    has_valid = valid is not None
    if has_valid:
        Xv = np.asarray(valid[0], np.float32)
        yv = np.asarray(valid[1], np.float32)
        binned_v_h = mapper.transform(Xv)   # host copy: resume replay walks
        binned_v = jnp.asarray(binned_v_h)
        scores_v = np.full((Xv.shape[0], 1), init_score, np.float32)
        walker = _cached(("walker", D, ()), lambda: make_binned_walker(D))
    best_metric = -np.inf if larger_better else np.inf
    best_iter = -1
    rounds_no_improve = 0

    level_growth = p.growth == "level"
    L = p.num_leaves                      # leaf slots
    I = L - 1                             # internal nodes
    if level_growth:
        from ..models.gbdt import perfect_tree_children
        lc_const, rc_const = perfect_tree_children(D)

    trees: Dict[str, List[np.ndarray]] = {k: [] for k in _STREAM_TREE_KEYS}
    tree_weights: List[float] = []
    bag_on = p.bagging_freq > 0 and p.bagging_fraction < 1.0
    ff_on = p.feature_fraction < 1.0
    mask_h = np.ones((n,), bool)
    bag_mask = None

    # ---- fault tolerance (ISSUE 10): periodic atomic checkpoints,
    # resume-through-replay, preemption-aware shutdown
    import contextlib
    from ..io.checkpoint import (CheckpointManager, book_reshard,
                                 check_resume_arg, resume_required_error,
                                 topology_stanza)
    from ..utils.resilience import PreemptionToken, preemption_scope
    # identity (must match) carries data/params only; the tile geometry is
    # the streamed driver's topology stanza — recorded, allowed to differ
    # on resume (elastic resume, ISSUE 14: the host that restarts a
    # preempted stream rarely has the old host-RAM budget)
    fingerprint = repr((sig, n, F, B, _content_fingerprint(cd.X)))
    _cur_topology = topology_stanza(shard_count=1,
                                    num_tiles=int(cd.num_tiles),
                                    tile_rows=int(T))
    check_resume_arg(resume, checkpoint_dir=checkpoint_dir)
    manager = None
    if checkpoint_dir:
        manager = CheckpointManager(checkpoint_dir,
                                    site="lightgbm.train_streamed",
                                    keep_last=checkpoint_keep_last)
    n_init_trees = 0
    start_iter = 0
    resumed_from = -1
    resharded = False
    preempted = False

    def _replay_range(t0: int, t1: int, valid_too: bool) -> None:
        """Replay stored trees [t0, t1) into the running scores with the
        EXACT float32 adds the live loop performs (host walks are pure
        integer ops), so a resumed run's state is bit-identical to the
        uninterrupted one's at the same iteration."""
        if t1 <= t0:
            return
        from ..models.gbdt import children_depth_bound
        depth_b = children_depth_bound(
            np.stack(trees["left_child"][t0:t1]),
            np.stack(trees["right_child"][t0:t1]))
        for t in range(t0, t1):
            sf_t, tb_t = trees["split_feature"][t], trees["threshold_bin"][t]
            lch_t, rch_t = trees["left_child"][t], trees["right_child"][t]
            lv_t = np.asarray(trees["leaf_value"][t], np.float32)
            w_t = float(tree_weights[t])
            leaf = _np_walk_tree(binned_h, sf_t, tb_t, lch_t, rch_t, depth_b)
            contrib = lv_t[leaf]
            if w_t != 1.0:
                contrib = (contrib * np.float32(w_t)).astype(np.float32)
            # in-place add (same ufunc the live loop's += runs) without
            # rebinding the closed-over array
            np.add(scores_h, contrib, out=scores_h)
            if valid_too and has_valid:
                leaf_v = _np_walk_tree(binned_v_h, sf_t, tb_t, lch_t, rch_t,
                                       depth_b)
                contrib_v = lv_t[leaf_v]
                if w_t != 1.0:
                    contrib_v = (contrib_v * np.float32(w_t)) \
                        .astype(np.float32)
                scores_v[:, 0] += contrib_v

    def _save_ckpt(finished: bool, block: bool = False) -> None:
        # snapshot on the training thread is just list copies + the PRNG
        # state dict; stacking + device-independent serialization + the
        # atomic publish all ride the manager's writer thread.  The one
        # completed-iteration convention (shared with train()): trees
        # grown beyond the warm-start prefix.
        done = len(tree_weights) - n_init_trees
        meta = _booster_ckpt_meta(done, n_init_trees, rng, best_metric,
                                  best_iter, rounds_no_improve, evals,
                                  init_score, fingerprint, finished,
                                  p.num_iterations, "streamed_booster_v1",
                                  topology=_cur_topology)
        manager.save(done, _booster_ckpt_arrays(trees, tree_weights,
                                                bag_mask), meta,
                     block=block)

    resumed = False
    if manager is not None and resume in ("auto", "must"):
        got = manager.load_latest(current_topology=_cur_topology)
        if got is None and resume == "must":
            raise resume_required_error(checkpoint_dir)
        if got is not None:
            _, _arrs, _meta = got
            if _meta.get("fingerprint") != fingerprint:
                raise ValueError(_CKPT_FINGERPRINT_MISMATCH)
            _delta = _meta.get("topology_delta")
            if _delta is not None and _delta["changed"]:
                # re-tiled resume: the row stream re-partitions onto this
                # run's tile geometry; with quantized histograms the
                # global-row-keyed rounding keeps the continued booster
                # bit-identical to an uninterrupted run at either width
                book_reshard("lightgbm.train_streamed", _delta)
                resharded = True
            T_done = int(_arrs["split_feature"].shape[0])
            for k in _STREAM_TREE_KEYS:
                trees[k] = [np.asarray(_arrs[k][t]) for t in range(T_done)]
            tree_weights[:] = [float(x) for x in _arrs["tree_weight"]]
            n_init_trees = int(_meta.get("n_init_trees", 0))
            rng.bit_generator.state = _meta["rng_state"]
            if "bag_mask" in _arrs:
                bag_mask = np.unpackbits(_arrs["bag_mask"])[:n].astype(bool)
            best_metric = float(_meta["best_metric"])
            best_iter = int(_meta["best_iter"])
            rounds_no_improve = int(_meta["rounds_no_improve"])
            evals[:] = [dict(e) for e in _meta.get("evals", [])]
            _replay_range(0, n_init_trees, valid_too=False)
            if float(_meta["init_score"]) != float(init_score):
                scores_h += np.float32(float(_meta["init_score"])
                                       - init_score)
                init_score = float(_meta["init_score"])
                if has_valid:
                    scores_v[:] = init_score
            _replay_range(n_init_trees, T_done, valid_too=True)
            resumed_from = int(_meta["iteration"])
            start_iter = resumed_from
            if _meta.get("finished") and \
                    p.num_iterations <= int(_meta.get("num_iterations",
                                                      resumed_from)):
                # the snapshot IS the finished run (early stop included):
                # skip the loop and return its booster; a LARGER
                # num_iterations target keeps training instead
                start_iter = p.num_iterations
            resumed = True
    if not resumed and init_booster is not None:
        # warm start (the substrate resume rides): replay the incoming
        # booster's trees on the host, matching train()'s machinery
        for t in range(init_booster.num_trees):
            for k in _STREAM_TREE_KEYS:
                trees[k].append(np.asarray(getattr(init_booster, k)[t]))
            tree_weights.append(float(init_booster.tree_weight[t]))
        n_init_trees = init_booster.num_trees
        _replay_range(0, n_init_trees, valid_too=False)
        if float(init_booster.init_score) != float(init_score):
            # shift base score AFTER replay (train() order), so continued
            # training optimizes against the recorded init_score
            scores_h += np.float32(init_booster.init_score - init_score)
            init_score = float(init_booster.init_score)
            if has_valid:
                scores_v[:] = init_score

    def _grad_pass():
        """First pass: gradients per tile (device), stored host-side, plus
        the GLOBAL grad/hess maxima every tile's quantization shares — the
        tile-level twin of the sharded pmax."""
        pf = _stream(lambda i, lo, hi: (pad_tile(scores_h, lo, hi, T),
                                        pad_tile(y, lo, hi, T),
                                        pad_tile(w, lo, hi, T)))
        gmax = hmax = 0.0
        with ambient_phase("ooc.gradients"):
            for i, lo, hi, (sc_t, y_t, w_t) in pf:
                g_t, h_t, gm, hm = grad_fn(sc_t, y_t, w_t)
                g_host[lo:hi] = np.asarray(g_t)[: hi - lo]
                h_host[lo:hi] = np.asarray(h_t)[: hi - lo]
                gmax = max(gmax, float(gm))
                hmax = max(hmax, float(hm))
        _finish_stream(pf)
        g_scale = max(gmax, 1e-12) / qg_cap
        h_scale = max(hmax, 1e-12) / qh_cap
        return float(g_scale), float(h_scale)

    def _route(lo, hi, bf, bb, do):
        """Host-side row routing (numerical splits): node -> 2*node + right,
        matching the level-wise grower's gather bit for bit."""
        node = node_h[lo:hi]
        f = np.maximum(bf[node], 0)
        rb = binned_h[lo:hi][np.arange(hi - lo), f].astype(np.int32)
        go_right = do[node] & (rb > bb[node])
        node_h[lo:hi] = 2 * node + go_right

    # per-iteration quantization mix (elastic resume): an exact-integer
    # fold of the HOST gradient arrays, so the value — and with it every
    # row's rounding noise — is identical under any tile width.  Written
    # once per iteration before the histogram passes read it.
    row_ids_h = np.arange(n, dtype=np.int32)
    _iter_mix = {"mix": np.int32(0)}

    def _hist_pass(nodes_d, gsc, hsc, decisions, node_of):
        """One accumulate pass over every tile: routing for this level
        (when ``decisions`` carries the previous level's splits) happens on
        the PREFETCH worker, then the consumer folds the tile's quantized
        partial into the int32 accumulator."""
        mixv = _iter_mix["mix"]

        def make_tile(i, lo, hi):
            if decisions is not None:
                _route(lo, hi, *decisions)
            node_t = np.where(mask_h[lo:hi], node_of(lo, hi),
                              -1).astype(np.int32)
            return (pad_tile(binned_h, lo, hi, T),
                    pad_tile(g_host, lo, hi, T),
                    pad_tile(h_host, lo, hi, T),
                    # node_t is already the slice: pad from its own origin
                    pad_tile(node_t, 0, hi - lo, T, fill=-1),
                    pad_tile(row_ids_h, lo, hi, T))
        acc = jnp.zeros((nodes_d, F, B, 3),
                        jnp.int32 if use_quant else jnp.float32)
        pf = _stream(make_tile)
        with ambient_phase("ooc.histogram"):
            for i, lo, hi, (b_t, g_t, h_t, n_t, i_t) in pf:
                acc = accum_fn(acc, b_t, g_t, h_t, n_t, i_t, mixv, gsc,
                               hsc)
        _finish_stream(pf)
        return acc

    # live monitor (ISSUE 19): one tick per boosting iteration.  The stall
    # watchdog covers the streamed passes too — a hung tile load freezes
    # the tick stream and trips as ``train_stall`` with the live
    # prefetcher snapshot showing ``waiting=True``.
    _watch = _wsrv = None
    if monitor_port is not None or monitor_stall_timeout_s is not None:
        from ..observability.trainwatch import start_training_monitor
        _watch, _wsrv = start_training_monitor(
            "lightgbm.train_streamed", total_steps=p.num_iterations,
            rows_per_step=n, monitor_port=monitor_port,
            stall_timeout_s=monitor_stall_timeout_s,
            driver="lightgbm.train_streamed")
        _watch.set_phase("boosting")
        _watch.set_prefetch_fn(_prefetch_state)

        def _watch_cb(i, ev, _w=_watch):
            val = None
            if ev:
                for k, v in ev.items():
                    if k != "iteration" and isinstance(v, (int, float)):
                        val = float(v)
                        break
            _w.tick(step=i + 1, loss=val)
        callbacks = list(callbacks or []) + [_watch_cb]

    # preemption scope only when checkpointing is on: without a durable
    # snapshot to write, a SIGTERM should keep its default behaviour
    _scope = preemption_scope() if manager is not None \
        else contextlib.nullcontext(PreemptionToken())
    _last_ckpt_iter = start_iter
    _trees_at_loop_start = len(tree_weights)
    with contextlib.ExitStack() as _stack:
      if _wsrv is not None:
          _stack.callback(_wsrv.stop)
      if _watch is not None:
          _stack.callback(_watch.close)
      _token = _stack.enter_context(_scope)
      if _watch is not None:
          _watch.set_preemption_token(_token)
      for it in range(start_iter, p.num_iterations):
        if _token.requested:
            # preempted: one final checkpoint at this iteration boundary,
            # then a clean partial return the caller can resume from
            _save_ckpt(finished=False, block=True)
            preempted = True
            break
        # ---- per-iteration host randomness (same semantics as train())
        feat_mask = np.ones((F,), bool)
        if ff_on:
            keep = max(1, int(round(p.feature_fraction * F)))
            feat_mask[:] = False
            feat_mask[rng.choice(F, size=keep, replace=False)] = True
        if bag_on and (it % p.bagging_freq == 0 or bag_mask is None):
            bag_mask = rng.random(n) < p.bagging_fraction
        mask_h = bag_mask if bag_on else np.ones((n,), bool)
        fm_dev = jnp.asarray(feat_mask)

        gsc, hsc = _grad_pass()
        if use_quant:
            _iter_mix["mix"] = _quant_mix(g_host, h_host)
        node_h = np.zeros((n,), np.int32)

        sf = np.full((I,), -1, np.int32)
        tb = np.zeros((I,), np.int32)
        th = np.zeros((I,), np.float32)
        sg = np.zeros((I,), np.float32)
        iv = np.zeros((I,), np.float32)
        ic = np.zeros((I,), np.float32)

        if level_growth:
            decisions = None
            for d in range(D):
                nodes_d = 2 ** d
                off = nodes_d - 1
                acc = _hist_pass(nodes_d, gsc, hsc, decisions,
                                 lambda lo, hi: node_h[lo:hi])
                bf_d, bb_d, do_d, gain_d, left_d, right_d, tot_d = [
                    np.asarray(a) for a in decide_fn(acc, gsc, hsc, fm_dev,
                                                     edge_ok_dev)]
                idx = off + np.arange(nodes_d)
                sf[idx] = np.where(do_d, bf_d, -1)
                tb[idx] = bb_d
                th[idx] = edges_np[bf_d, np.clip(bb_d, 0, B - 2)]
                sg[idx] = np.where(do_d, gain_d, 0.0)
                iv[idx] = _np_leaf_output(tot_d[:, 0], tot_d[:, 1], l1, l2,
                                          max_delta)
                ic[idx] = tot_d[:, 2]
                decisions = (bf_d, bb_d, do_d)
            # final routing (level D decisions) over the whole host array
            _route(0, n, *decisions)
            lv2 = np.stack([_np_leaf_output(left_d[:, 0], left_d[:, 1], l1,
                                            l2, max_delta),
                            _np_leaf_output(right_d[:, 0], right_d[:, 1],
                                            l1, l2, max_delta)],
                           axis=1).reshape(L)
            lc2 = np.stack([left_d[:, 2], right_d[:, 2]], axis=1).reshape(L)
            leaf_value = np.where(lc2 > 0, lv2, 0.0).astype(np.float32)
            leaf_count = lc2.astype(np.float32)
            leaf_of_row = node_h
            lch, rch = lc_const, rc_const
        else:
            (sf, tb, th, sg, iv, ic, leaf_value, leaf_count, lch, rch,
             leaf_of_row) = _grow_leafwise_streamed(
                p, n, F, B, T, D, gsc, hsc, fm_dev, edge_ok_dev, node_h,
                mask_h, binned_h, edges_np, _hist_pass, leaf_best_fn, l1,
                l2, max_delta)

        lv_s = (leaf_value * lr).astype(np.float32)
        scores_h += lv_s[leaf_of_row]
        for k_name, arr in zip(
                _STREAM_TREE_KEYS,
                (lch, rch, sf, th, tb, sg, iv, ic, lv_s, leaf_count)):
            trees[k_name].append(np.asarray(arr))
        tree_weights.append(1.0)

        if has_valid:
            with ambient_phase("ooc.eval"):
                leaf_v = np.asarray(walker(
                    binned_v, jnp.asarray(sf), jnp.asarray(tb),
                    jnp.asarray(np.asarray(lch, np.int32)),
                    jnp.asarray(np.asarray(rch, np.int32))))
                scores_v[:, 0] += lv_s[leaf_v]
                m = metric_fn(yv, scores_v.astype(np.float64))
            evals.append({metric_name: m, "iteration": it})
            improved = m > best_metric if larger_better else m < best_metric
            if improved:
                best_metric, best_iter, rounds_no_improve = m, it, 0
            else:
                rounds_no_improve += 1
            if p.early_stopping_round > 0 and \
                    rounds_no_improve >= p.early_stopping_round:
                break
        if callbacks:
            for cb in callbacks:
                cb(it, evals[-1] if evals else None)
        if manager is not None and checkpoint_every > 0 \
                and it + 1 - _last_ckpt_iter >= checkpoint_every:
            _save_ckpt(finished=False)
            _last_ckpt_iter = it + 1

    if manager is not None:
        if not preempted and (len(tree_weights) > _trees_at_loop_start
                              or not resumed):
            # terminal snapshot (covers early stopping too): resume of a
            # finished run restores the final booster instead of
            # re-training the tail.  A finished-run restore that grew
            # nothing skips the redundant re-save.
            _save_ckpt(finished=True, block=True)
        manager.close()

    if p.growth == "leaf":
        from ..models.gbdt import children_depth_bound
        D = children_depth_bound(np.stack(trees["left_child"]),
                                 np.stack(trees["right_child"]))
    booster = GBDTBooster(
        np.stack(trees["split_feature"]), np.stack(trees["threshold"]),
        np.stack(trees["threshold_bin"]), np.stack(trees["split_gain"]),
        np.stack(trees["internal_value"]),
        np.stack(trees["internal_count"]),
        np.stack(trees["leaf_value"]), np.stack(trees["leaf_count"]),
        np.asarray(tree_weights, np.float32),
        left_child=np.stack(trees["left_child"]),
        right_child=np.stack(trees["right_child"]),
        max_depth=D, num_features=F, objective=p.objective, num_class=1,
        init_score=init_score, feature_names=feature_names,
        best_iteration=best_iter, sigmoid=p.sigmoid)

    busy = stream_totals["wait_s"] + stream_totals["compute_s"]
    extras = {
        "num_tiles": float(cd.num_tiles), "tile_rows": float(T),
        "prefetch_wait_s": round(stream_totals["wait_s"], 6),
        "tile_compute_s": round(stream_totals["compute_s"], 6),
        "tiles_streamed": stream_totals["tiles"],
        "prefetch_overlap_pct": round(
            100.0 * stream_totals["compute_s"] / busy, 2) if busy > 0
        else 100.0,
        "quantized": float(use_quant),
    }
    if manager is not None:
        extras["preempted"] = float(preempted)
        extras["resumed_from_iteration"] = float(resumed_from)
        extras["checkpoint_saves"] = float(manager.saves_ok)
        extras["resharded"] = float(resharded)
    for k, v in extras.items():
        _span.set_attribute(f"ooc.{k}", v)
    _span.set_attribute("rows", n)
    _span.set_attribute("features", F)
    _span.set_attribute("iterations", len(tree_weights))
    export_span(_span)
    return TrainResult(booster=booster, evals=evals, bin_mapper=mapper,
                       extras=extras)


def _grow_leafwise_streamed(p, n, F, B, T, depth_bound, gsc, hsc, fm_dev,
                            edge_ok_dev, node_h, mask_h, binned_h, edges_np,
                            hist_pass, leaf_best_fn, l1, l2, max_delta):
    """One leaf-wise tree over the tile stream: LightGBM's best-first
    growth with the histogram passes streamed.  Per split step the LEFT
    child's histogram is rebuilt with one accumulate pass over every tile
    (``hist_pass`` with a single node) and the sibling comes from exact
    integer subtraction against a host-resident stored-histogram table —
    the same histogram-halving the in-memory grower runs, with the storage
    moved off-device (out-of-core all the way down).  Bookkeeping mirrors
    ``make_leafwise_grower.step`` in host numpy; a step whose best gain
    fails ``min_gain_to_split`` ends the tree (later steps could only see
    smaller global-best gains)."""
    import jax.numpy as jnp

    L, M = p.num_leaves, p.num_leaves - 1
    depth_cap = p.max_depth
    min_gain = p.min_gain_to_split
    stored = np.zeros((L, F, B, 3),
                      np.int32 if p.use_quantized_grad else np.float32)

    lc_arr = np.full((M,), -1, np.int32)
    rc_arr = np.full((M,), -1, np.int32)
    sf = np.full((M,), -1, np.int32)
    tb = np.zeros((M,), np.int32)
    th = np.zeros((M,), np.float32)
    sg = np.zeros((M,), np.float32)
    iv = np.zeros((M,), np.float32)
    ic = np.zeros((M,), np.float32)
    leaf_tot = np.zeros((L, 3), np.float32)
    leaf_depth = np.zeros((L,), np.int32)
    created = np.zeros((L,), bool)
    created[0] = True
    leaf_parent = np.full((L,), -1, np.int32)
    leaf_side = np.zeros((L,), np.int32)
    best_gain = np.full((L,), -np.inf, np.float32)
    best_feat = np.zeros((L,), np.int32)
    best_bin = np.zeros((L,), np.int32)
    best_left = np.zeros((L, 3), np.float32)

    def depth_ok_of(d):
        return True if depth_cap <= 0 else bool(d < depth_cap)

    def candidates(hist_np, slot, dok):
        g, f, b, left, tot = leaf_best_fn(jnp.asarray(hist_np), gsc, hsc,
                                          fm_dev, dok, edge_ok_dev)
        best_gain[slot] = float(g)
        best_feat[slot] = int(f)
        best_bin[slot] = int(b)
        best_left[slot] = np.asarray(left)
        return np.asarray(tot)

    # root: one streamed pass with a single node id
    h_root = np.asarray(hist_pass(1, gsc, hsc, None,
                                  lambda lo, hi: np.zeros((hi - lo,),
                                                          np.int32)))[0]
    stored[0] = h_root
    leaf_tot[0] = candidates(h_root, 0, depth_ok_of(0))

    for s in range(M):
        j = int(np.argmax(best_gain))
        if not best_gain[j] > min_gain:
            break
        new_leaf = s + 1
        f, b = int(best_feat[j]), int(best_bin[j])
        tot = leaf_tot[j].copy()

        sf[s] = f
        tb[s] = b
        th[s] = edges_np[f, min(max(b, 0), B - 2)]
        sg[s] = best_gain[j]
        iv[s] = _np_leaf_output(tot[0:1], tot[1:2], l1, l2, max_delta)[0]
        ic[s] = tot[2]

        pn, side = leaf_parent[j], leaf_side[j]
        if pn >= 0:
            (lc_arr if side == 0 else rc_arr)[pn] = s
        lc_arr[s] = -(j + 1)
        rc_arr[s] = -(new_leaf + 1)
        leaf_parent[j], leaf_side[j] = s, 0
        leaf_parent[new_leaf], leaf_side[new_leaf] = s, 1
        created[new_leaf] = True

        # route leaf j's rows (whole host array: one vectorized pass)
        in_j = node_h == j
        go_right = in_j & (binned_h[:, f].astype(np.int32) > b)
        node_h[go_right] = new_leaf

        left_stats = best_left[j].copy()
        leaf_tot[j] = left_stats
        leaf_tot[new_leaf] = tot - left_stats
        d_new = leaf_depth[j] + 1
        leaf_depth[j] = leaf_depth[new_leaf] = d_new

        # left child rebuilt over the stream; sibling by exact subtraction
        hl = np.asarray(hist_pass(
            1, gsc, hsc, None,
            lambda lo, hi: np.where(node_h[lo:hi] == j, 0, -1)
            .astype(np.int32)))[0]
        hr = stored[j] - hl
        stored[j], stored[new_leaf] = hl, hr

        dok = depth_ok_of(d_new)
        candidates(hl, j, dok)
        candidates(hr, new_leaf, dok)

    lv = _np_leaf_output(leaf_tot[:, 0], leaf_tot[:, 1], l1, l2, max_delta)
    leaf_value = np.where(created, lv, 0.0).astype(np.float32)
    leaf_count = np.where(created, leaf_tot[:, 2], 0.0).astype(np.float32)
    return (sf, tb, th, sg, iv, ic, leaf_value, leaf_count, lc_arr, rc_arr,
            node_h.copy())
