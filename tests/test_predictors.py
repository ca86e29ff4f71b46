"""Predictor suite tests + hypothesis properties (paper core: KNN/DT/RF)."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; plain unit tests still run
    from _hypothesis_stub import given, settings, st

from repro.core import predictors as P

RNG = np.random.default_rng(3)


def _synthetic(n=800, d=4, noise=0.02):
    X = RNG.uniform(0.5, 4.0, (n, d)).astype(np.float32)
    # multiplicative ground truth (like power ~ util * f^3): log-linear
    y = 5.0 * X[:, 0] * X[:, 1] ** 2 / X[:, 2] + X[:, 3]
    y = y * np.exp(RNG.normal(0, noise, n))
    return X, y


@pytest.mark.parametrize("name", ["knn", "decision_tree", "random_forest"])
def test_fits_synthetic_with_low_mape(name):
    X, y = _synthetic()
    res = P.kfold_evaluate(name, X, y, k=4)
    assert res["mape"] < 25.0, res
    assert res["r2"] > 0.82, res


def test_random_forest_beats_single_tree_on_noise():
    X, y = _synthetic(noise=0.15)
    tree = P.kfold_evaluate("decision_tree", X, y, k=4)
    forest = P.kfold_evaluate("random_forest", X, y, k=4)
    assert forest["mape"] <= tree["mape"] * 1.25


def test_metrics_match_definitions():
    y, p = np.array([1.0, 2.0, 4.0]), np.array([1.1, 1.8, 4.4])
    assert abs(P.mape(y, p) - 100 * np.mean([0.1, 0.1, 0.1])) < 1e-6
    assert abs(P.r2_score(y, y) - 1.0) < 1e-12


@settings(max_examples=25, deadline=None)
@given(st.integers(10, 60), st.integers(2, 5))
def test_tree_predictions_within_training_range(n, d):
    """CART leaves are means of training targets: predictions are bounded by
    the training target range (a safety property for the DSE ranking)."""
    rng = np.random.default_rng(n * 17 + d)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = np.abs(rng.normal(size=n)) + 0.1
    m = P.DecisionTreeRegressor(max_depth=6).fit(X, y)
    pred = m.predict(rng.normal(size=(32, d)).astype(np.float32))
    assert pred.min() >= y.min() / 1.001
    assert pred.max() <= y.max() * 1.001


@settings(max_examples=20, deadline=None)
@given(st.integers(5, 40))
def test_knn_k1_interpolates_training_points(n):
    rng = np.random.default_rng(n)
    X = rng.uniform(1, 10, (n, 4)).astype(np.float32)
    y = np.abs(rng.normal(size=n)).astype(np.float64) + 0.5
    m = P.KNNRegressor(k=1).fit(X, y)
    pred = m.predict(X)
    np.testing.assert_allclose(pred, y, rtol=5e-3)


def test_knn_row_blocks_match_direct_formula():
    """Prediction in ``KNN_BATCH`` row blocks (with a partial last block)
    equals the one-shot [N, n_train] distance formula."""
    X, y = _synthetic(n=300)
    m = P.KNNRegressor().fit(X, y)
    Q = RNG.uniform(0.5, 4.0, (2 * P.KNN_BATCH + 37, X.shape[1]))
    Qz = (np.log1p(np.abs(Q.astype(np.float32))) - np.asarray(m._mu)) \
        / np.asarray(m._sd)
    d2 = ((Qz[:, None, :] - np.asarray(m._x)[None]) ** 2).sum(-1)
    near = np.argsort(d2, axis=1, kind="stable")[:, :m.k]
    w = 1.0 / (np.sqrt(np.take_along_axis(d2, near, 1)) + 1e-6)
    want = np.exp((w / w.sum(1, keepdims=True)
                   * np.asarray(m._y)[near]).sum(1))
    np.testing.assert_allclose(m.predict(Q), want, rtol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.floats(1.1, 3.0), st.floats(0.1, 0.9))
def test_predictor_scale_monotonicity(a, b):
    """Scaling a feature the target grows with must not DECREASE prediction
    on average (sanity for DVFS-style sweeps)."""
    n = 200
    rng = np.random.default_rng(int(a * 100) + int(b * 10))
    X = rng.uniform(0.5, 2.0, (n, 3)).astype(np.float32)
    y = X[:, 0] ** 3 * 10 + 1.0
    m = P.RandomForestRegressor(n_trees=15, max_depth=8).fit(X, y)
    lo = X.copy(); lo[:, 0] = 0.7
    hi = X.copy(); hi[:, 0] = 1.8
    assert m.predict(hi).mean() > m.predict(lo).mean()


# --- warm-start (partial_fit) forest: incremental refits for the adaptive
# --- campaign loop -----------------------------------------------------------


def _rows(seed, n=60, d=4):
    rng = np.random.default_rng(seed)
    X = rng.uniform(0.5, 4.0, (n, d)).astype(np.float32)
    y = 5.0 * X[:, 0] * X[:, 1] ** 2 / X[:, 2] + X[:, 3]
    return X, y


def _warm_forest():
    return P.RandomForestRegressor(n_trees=8, max_depth=6, min_leaf=2,
                                   refresh_trees=3, log_target=True)


def test_partial_fit_same_call_sequence_is_bitwise_deterministic():
    Xq = _rows(99)[0][:16]
    preds = []
    for _ in range(2):
        m = _warm_forest()
        for step, seed in enumerate([7, 11, 13]):
            m.partial_fit(*_rows(step), seed=seed)
        preds.append(np.asarray(m.predict(Xq)))
    np.testing.assert_array_equal(preds[0], preds[1])


def test_partial_fit_accumulates_rows_and_cycles_refresh_slots():
    m = _warm_forest()
    X0, y0 = _rows(0)
    m.partial_fit(X0, y0, seed=1)
    assert m.n_rows == len(X0)
    cold = [t for t in m._trees]
    X1, y1 = _rows(1)
    m.partial_fit(X1, y1, seed=1)
    assert m.n_rows == len(X0) + len(X1)
    # exactly refresh_trees slots rebuilt, starting at slot 0
    changed = [i for i, (a, b) in enumerate(zip(cold, m._trees)) if a is not b]
    assert changed == [0, 1, 2]
    warm1 = [t for t in m._trees]
    m.partial_fit(*_rows(2), seed=1)
    changed = [i for i, (a, b) in enumerate(zip(warm1, m._trees))
               if a is not b]
    assert changed == [3, 4, 5]


def test_partial_fit_refreshed_trees_see_new_rows():
    m = _warm_forest()
    m.partial_fit(*_rows(0, n=40), seed=5)
    before = np.asarray(m.predict(_rows(42)[0][:8]))
    # feed rows from a shifted distribution: refreshed trees must move
    rng = np.random.default_rng(8)
    X = rng.uniform(0.5, 4.0, (80, 4)).astype(np.float32)
    m.partial_fit(X, np.full(80, 1e-3), seed=5)
    after = np.asarray(m.predict(_rows(42)[0][:8]))
    assert not np.array_equal(before, after)
    assert after.mean() < before.mean()


def test_fit_resets_warm_state():
    m = _warm_forest()
    m.partial_fit(*_rows(0), seed=2)
    m.partial_fit(*_rows(1), seed=2)
    X2, y2 = _rows(2)
    m.fit(X2, y2)
    assert m.n_rows == len(X2)
    # next partial_fit behaves like the first warm call again: slot 0 onward
    cold = [t for t in m._trees]
    m.partial_fit(*_rows(3), seed=2)
    changed = [i for i, (a, b) in enumerate(zip(cold, m._trees)) if a is not b]
    assert changed == [0, 1, 2]


def test_predict_log_stats_mean_matches_predict():
    m = _warm_forest()
    m.partial_fit(*_rows(4), seed=3)
    Xq = _rows(5)[0][:24]
    mu, sd = m.predict_log_stats(Xq)
    assert mu.shape == sd.shape == (24,)
    assert np.all(sd >= 0.0)
    np.testing.assert_allclose(np.exp(mu), np.asarray(m.predict(Xq)),
                               rtol=1e-5)


def test_predict_log_stats_zero_spread_on_duplicate_target():
    # all-identical targets: every tree predicts the same constant
    X = _rows(6, n=32)[0]
    m = _warm_forest()
    m.partial_fit(X, np.full(32, 7.0), seed=0)
    mu, sd = m.predict_log_stats(X[:8])
    np.testing.assert_allclose(mu, np.log(7.0), rtol=1e-6)
    np.testing.assert_allclose(sd, 0.0, atol=1e-7)
