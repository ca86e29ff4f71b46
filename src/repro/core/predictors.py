"""The paper's ML predictor suite: KNN, Decision Tree (CART), Random Forest.

The paper trains "multiple machine learning models (e.g., K-Nearest Neighbor,
Decision Tree, Random Forest Tree) for each specific task (i.e., power or
performance prediction)" and picks the best per task.  Reported: Random Forest
power MAPE 5.03% / R^2 0.9561; KNN cycles MAPE 5.94%.

Implementation notes:
  * Tree FITTING is plain numpy (recursive CART, variance-reduction splits) —
    fitting is host-side and tiny.
  * Tree INFERENCE is vectorized: flattened (feature, threshold, child, leaf)
    arrays walked level-by-level in jnp — jit-able so DSE sweeps can evaluate
    thousands of design points per millisecond (the paper's "fast" claim).
  * KNN is pure jnp (z-scored features, inverse-distance-weighted top-k).
  * Targets are trained in log space: power and especially cycles span orders
    of magnitude across the design space; MAPE is computed in linear space.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# --- metrics -------------------------------------------------------------------------

def mape(y_true, y_pred) -> float:
    y_true, y_pred = np.asarray(y_true, np.float64), np.asarray(y_pred, np.float64)
    return float(np.mean(np.abs((y_pred - y_true) / np.maximum(np.abs(y_true), 1e-12))) * 100)


def r2_score(y_true, y_pred) -> float:
    y_true, y_pred = np.asarray(y_true, np.float64), np.asarray(y_pred, np.float64)
    ss_res = np.sum((y_true - y_pred) ** 2)
    ss_tot = np.sum((y_true - y_true.mean()) ** 2)
    return float(1.0 - ss_res / max(ss_tot, 1e-12))


# --- KNN -------------------------------------------------------------------------------

@dataclasses.dataclass
class KNNRegressor:
    k: int = 5
    log_target: bool = True
    _x: Optional[jnp.ndarray] = None
    _y: Optional[jnp.ndarray] = None
    _mu: Optional[jnp.ndarray] = None
    _sd: Optional[jnp.ndarray] = None

    def fit(self, X, y):
        # features span orders of magnitude (tokens, flops): distance in
        # log1p space, then z-scored
        X = jnp.log1p(jnp.abs(jnp.asarray(X, jnp.float32)))
        y = jnp.asarray(y, jnp.float32)
        self._mu = X.mean(0)
        self._sd = jnp.maximum(X.std(0), 1e-6)
        self._x = (X - self._mu) / self._sd
        self._y = jnp.log(jnp.maximum(y, 1e-12)) if self.log_target else y
        return self

    def predict(self, X):
        X = jnp.log1p(jnp.abs(jnp.asarray(X, jnp.float32)))
        X = (X - self._mu) / self._sd
        pred = _knn_predict_jnp(self._x, self._y, X,
                                k=min(self.k, self._x.shape[0]))
        return np.asarray(jnp.exp(pred) if self.log_target else pred)


# query rows per step of the KNN scan: the distance block is [KNN_BATCH,
# n_train], so a design-space-sized query never holds [N, n_train, F]
KNN_BATCH = 1024


@functools.partial(jax.jit, static_argnames=("k",))
def _knn_predict_jnp(x_train, y_train, X, *, k: int):
    """Inverse-distance-weighted mean of the ``k`` nearest training targets
    for each row of ``X`` (all inputs already z-scored), in row blocks."""
    def one(row):
        d2 = jnp.sum((row[None, :] - x_train) ** 2, axis=-1)
        neg_d2, idx = jax.lax.top_k(-d2, k)
        w = 1.0 / (jnp.sqrt(-neg_d2) + 1e-6)
        return jnp.sum(w / jnp.sum(w) * y_train[idx])
    return jax.lax.map(one, X, batch_size=KNN_BATCH)


# --- CART decision tree ------------------------------------------------------------------

@dataclasses.dataclass
class _TreeArrays:
    feature: np.ndarray      # int32 [n_nodes]; -1 => leaf
    threshold: np.ndarray    # float32
    left: np.ndarray         # int32 child indices
    right: np.ndarray
    value: np.ndarray        # float32 leaf predictions


def _build_cart(X: np.ndarray, y: np.ndarray, max_depth: int, min_leaf: int,
                rng: np.random.Generator, feature_frac: float) -> _TreeArrays:
    nodes: List[dict] = []

    def grow(idx: np.ndarray, depth: int) -> int:
        node_id = len(nodes)
        nodes.append({})
        yi = y[idx]
        if depth >= max_depth or idx.size < 2 * min_leaf or np.ptp(yi) < 1e-12:
            nodes[node_id] = {"leaf": float(yi.mean())}
            return node_id
        n_feat = X.shape[1]
        feats = rng.choice(n_feat, max(1, int(n_feat * feature_frac)), replace=False)
        best = None
        parent_var = yi.var() * idx.size
        for f in feats:
            xs = X[idx, f]
            order = np.argsort(xs, kind="stable")
            xs_s, ys_s = xs[order], yi[order]
            csum = np.cumsum(ys_s)
            csq = np.cumsum(ys_s ** 2)
            n = idx.size
            split_pts = np.nonzero(np.diff(xs_s) > 1e-12)[0] + 1
            split_pts = split_pts[(split_pts >= min_leaf) & (split_pts <= n - min_leaf)]
            if split_pts.size == 0:
                continue
            nl = split_pts.astype(np.float64)
            sl, sq_l = csum[split_pts - 1], csq[split_pts - 1]
            var_l = sq_l - sl ** 2 / nl
            sr, sq_r = csum[-1] - sl, csq[-1] - sq_l
            var_r = sq_r - sr ** 2 / (n - nl)
            score = var_l + var_r
            j = int(np.argmin(score))
            if best is None or score[j] < best[0]:
                thr = 0.5 * (xs_s[split_pts[j] - 1] + xs_s[split_pts[j]])
                best = (float(score[j]), int(f), float(thr))
        if best is None or best[0] >= parent_var - 1e-12:
            nodes[node_id] = {"leaf": float(yi.mean())}
            return node_id
        _, f, thr = best
        mask = X[idx, f] <= thr
        li = grow(idx[mask], depth + 1)
        ri = grow(idx[~mask], depth + 1)
        nodes[node_id] = {"feature": f, "threshold": thr, "left": li, "right": ri}
        return node_id

    grow(np.arange(X.shape[0]), 0)
    n = len(nodes)
    arr = _TreeArrays(
        feature=np.full(n, -1, np.int32), threshold=np.zeros(n, np.float32),
        left=np.zeros(n, np.int32), right=np.zeros(n, np.int32),
        value=np.zeros(n, np.float32))
    for i, nd in enumerate(nodes):
        if "leaf" in nd:
            arr.value[i] = nd["leaf"]
        else:
            arr.feature[i] = nd["feature"]
            arr.threshold[i] = nd["threshold"]
            arr.left[i] = nd["left"]
            arr.right[i] = nd["right"]
    return arr


@functools.partial(jax.jit, static_argnames=("max_depth",))
def _forest_predict_jnp(feat, thr, left, right, val, X, *, max_depth: int):
    """Level-synchronous walk of T stacked trees over N samples, jitted.

    feat/thr/left/right/val: [T, n_nodes] padded per-tree arrays; X: [N, F].
    Returns [T, N] leaf values.  One compiled kernel evaluates the whole
    forest x design-space batch — the paper's "microseconds per point" path.
    """
    T, N = feat.shape[0], X.shape[0]
    node = jnp.zeros((T, N), jnp.int32)
    sample = jnp.arange(N)[None, :]

    def step(node, _):
        f = jnp.take_along_axis(feat, node, axis=1)          # [T, N]
        is_leaf = f < 0
        x = X[sample, jnp.maximum(f, 0)]                     # [T, N]
        nxt = jnp.where(x <= jnp.take_along_axis(thr, node, axis=1),
                        jnp.take_along_axis(left, node, axis=1),
                        jnp.take_along_axis(right, node, axis=1))
        return jnp.where(is_leaf, node, nxt), None

    node, _ = jax.lax.scan(step, node, None, length=max_depth + 1)
    return jnp.take_along_axis(val, node, axis=1)


def _stack_trees(trees: List[_TreeArrays]) -> tuple:
    """Pad every tree to the forest's max node count and stack [T, n_nodes]."""
    m = max(t.feature.shape[0] for t in trees)
    pad = lambda a, fill: np.stack(
        [np.concatenate([x, np.full(m - x.shape[0], fill, x.dtype)])
         for x in a])
    return (pad([t.feature for t in trees], -1),
            pad([t.threshold for t in trees], 0.0),
            pad([t.left for t in trees], 0),
            pad([t.right for t in trees], 0),
            pad([t.value for t in trees], 0.0))


def _tree_predict_jnp(arr: _TreeArrays, X: jnp.ndarray, max_depth: int) -> jnp.ndarray:
    out = _forest_predict_jnp(arr.feature[None], arr.threshold[None],
                              arr.left[None], arr.right[None], arr.value[None],
                              X, max_depth=max_depth)
    return out[0]


@dataclasses.dataclass
class DecisionTreeRegressor:
    max_depth: int = 12
    min_leaf: int = 2
    log_target: bool = True
    _tree: Optional[_TreeArrays] = None

    def fit(self, X, y, seed: int = 0):
        X = np.asarray(X, np.float32)
        y = np.asarray(y, np.float64)
        yt = np.log(np.maximum(y, 1e-12)) if self.log_target else y
        self._tree = _build_cart(X, yt, self.max_depth, self.min_leaf,
                                 np.random.default_rng(seed), 1.0)
        return self

    def predict(self, X):
        p = _tree_predict_jnp(self._tree, jnp.asarray(X, jnp.float32), self.max_depth)
        p = np.asarray(p, np.float64)
        return np.exp(p) if self.log_target else p


@dataclasses.dataclass
class RandomForestRegressor:
    """The paper's random forest, extended with a warm-start surface for
    active-learning loops (``repro.dse_campaign.adaptive``):

    * ``partial_fit`` appends new rows and rebuilds only ``refresh_trees``
      tree slots per call (cycling through the forest), so per-round refits
      cost a fraction of a full ``fit`` while every tree eventually sees the
      accumulated data;
    * ``predict_log_stats`` exposes the per-tree prediction spread — the
      forest-variance exploration term of the acquisition function.

    Both are seeded-deterministic: tree slot ``t`` rebuilt on the ``c``-th
    ``partial_fit`` call draws its bootstrap from ``default_rng((seed, c,
    t))``, so replaying the same call sequence (same data, same seeds)
    reproduces the forest bitwise — the property that makes adaptive
    checkpoint/resume able to reconstruct the surrogate state exactly.
    """

    n_trees: int = 40
    max_depth: int = 12
    min_leaf: int = 2
    feature_frac: float = 0.7
    log_target: bool = True
    refresh_trees: Optional[int] = None      # per-partial_fit rebuild budget
    _trees: Optional[List[_TreeArrays]] = None
    _stacked: Optional[tuple] = None
    _X: Optional[np.ndarray] = None          # accumulated warm-start rows
    _y: Optional[np.ndarray] = None          # (transformed target space)
    _fit_calls: int = 0
    _next_slot: int = 0

    def _transform_y(self, y: np.ndarray) -> np.ndarray:
        return np.log(np.maximum(y, 1e-12)) if self.log_target else y

    def fit(self, X, y, seed: int = 0):
        X = np.asarray(X, np.float32)
        y = np.asarray(y, np.float64)
        yt = self._transform_y(y)
        rng = np.random.default_rng(seed)
        self._trees = []
        n = X.shape[0]
        for _ in range(self.n_trees):
            boot = rng.integers(0, n, n)                    # bootstrap sample
            self._trees.append(_build_cart(X[boot], yt[boot], self.max_depth,
                                           self.min_leaf, rng, self.feature_frac))
        self._stacked = _stack_trees(self._trees)
        # a full fit resets the warm-start state (the incremental history is
        # superseded by the from-scratch forest)
        self._X, self._y = X, yt
        self._fit_calls, self._next_slot = 1, 0
        return self

    @property
    def n_rows(self) -> int:
        """Accumulated training rows (warm-start surface)."""
        return 0 if self._X is None else int(self._X.shape[0])

    def partial_fit(self, X, y, seed: int = 0):
        """Warm-start incremental refit: append ``(X, y)`` to the accumulated
        training set, then rebuild only ``refresh_trees`` tree slots
        (cyclically; ``None`` rebuilds all) on the FULL accumulated data.

        The first call builds the whole forest.  Each rebuilt slot's
        bootstrap is drawn from ``default_rng((seed, call_index, slot))`` —
        independent of which slots any other call rebuilt — so a replayed
        call sequence reproduces the forest bitwise (tested in
        ``tests/test_predictors.py``).  Untouched slots keep their exact
        tree arrays: they were fitted on less data, which is the
        staleness-for-speed trade the adaptive campaign's per-round refit
        makes.
        """
        X = np.asarray(X, np.float32)
        yt = self._transform_y(np.asarray(y, np.float64))
        if X.ndim != 2 or X.shape[0] != yt.shape[0]:
            raise ValueError(f"partial_fit shapes: X {X.shape} vs y {yt.shape}")
        if self._X is None:
            self._X, self._y = X, yt
        else:
            if X.shape[1] != self._X.shape[1]:
                raise ValueError(
                    f"partial_fit feature width {X.shape[1]} != accumulated "
                    f"{self._X.shape[1]}")
            self._X = np.concatenate([self._X, X])
            self._y = np.concatenate([self._y, yt])
        n = self._X.shape[0]
        if self._trees is None:
            self._trees = [None] * self.n_trees
            slots = list(range(self.n_trees))               # cold: build all
        else:
            k = self.n_trees if self.refresh_trees is None else min(
                max(int(self.refresh_trees), 1), self.n_trees)
            slots = [(self._next_slot + i) % self.n_trees for i in range(k)]
            self._next_slot = (slots[-1] + 1) % self.n_trees
        for t in slots:
            rng = np.random.default_rng((seed, self._fit_calls, t))
            boot = rng.integers(0, n, n)
            self._trees[t] = _build_cart(self._X[boot], self._y[boot],
                                         self.max_depth, self.min_leaf, rng,
                                         self.feature_frac)
        self._fit_calls += 1
        self._stacked = _stack_trees(self._trees)
        return self

    def _tree_preds(self, X) -> jnp.ndarray:
        if self._stacked is None:           # fitted by an older pickle/caller
            self._stacked = _stack_trees(self._trees)
        return _forest_predict_jnp(*self._stacked,
                                   jnp.asarray(X, jnp.float32),
                                   max_depth=self.max_depth)

    def predict(self, X):
        p = np.asarray(jnp.mean(self._tree_preds(X), axis=0), np.float64)
        return np.exp(p) if self.log_target else p

    def predict_log_stats(self, X) -> Tuple[np.ndarray, np.ndarray]:
        """Per-sample (mean, std) over the per-tree predictions, in the
        model's TRAINING target space (log space when ``log_target``) — the
        spread is the epistemic-uncertainty reading the adaptive campaign's
        exploration term consumes.  ``exp(mean)`` equals ``predict``."""
        preds = np.asarray(self._tree_preds(X), np.float64)   # [T, N]
        return preds.mean(axis=0), preds.std(axis=0)


MODELS = {
    "knn": lambda: KNNRegressor(k=5),
    "decision_tree": lambda: DecisionTreeRegressor(),
    "random_forest": lambda: RandomForestRegressor(),
}


def kfold_evaluate(model_name: str, X, y, k: int = 5, seed: int = 0) -> dict:
    """K-fold CV -> mean MAPE / R^2 (the paper's model-selection metric)."""
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float64)
    n = X.shape[0]
    idx = np.random.default_rng(seed).permutation(n)
    folds = np.array_split(idx, k)
    mapes, r2s = [], []
    for i in range(k):
        test = folds[i]
        train = np.concatenate([folds[j] for j in range(k) if j != i])
        m = MODELS[model_name]()
        m.fit(X[train], y[train])
        pred = m.predict(X[test])
        mapes.append(mape(y[test], pred))
        r2s.append(r2_score(y[test], pred))
    return {"model": model_name, "mape": float(np.mean(mapes)),
            "r2": float(np.mean(r2s)), "mape_std": float(np.std(mapes))}
