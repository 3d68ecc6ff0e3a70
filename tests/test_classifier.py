"""Classifier zoo tests: DT/LR correctness, feature selection, DTree
lowering to the simulator's fixed arrays."""
import jax.numpy as jnp
import numpy as np

from hyp_compat import hypothesis, st
from repro.core import classifier as clf
from repro.core.simulator import DTree


def _toy(n=2000, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype(np.float32)
    y = ((x[:, 0] > 0.3) & (x[:, 2] < 0.5)).astype(np.int32)
    return x, y


def test_dt_learns_axis_aligned_concept():
    x, y = _toy()
    t = clf.DecisionTree.fit(x, y, depth=2)
    assert t.accuracy(x, y) > 0.95


def test_dt_depth1_weaker_than_depth2():
    x, y = _toy()
    t1 = clf.DecisionTree.fit(x, y, depth=1)
    t2 = clf.DecisionTree.fit(x, y, depth=2)
    assert t2.accuracy(x, y) >= t1.accuracy(x, y) - 1e-9


def test_dt_storage_grows_with_depth():
    x, y = _toy(4000)
    t2 = clf.DecisionTree.fit(x, y, depth=2)
    t8 = clf.DecisionTree.fit(x, y, depth=8, class_weight=None)
    assert t8.storage_kb() >= t2.storage_kb()
    assert t2.n_nodes() <= 7


def test_depth2_array_lowering_matches_host_predict():
    x, y = _toy()
    t = clf.DecisionTree.fit(x, y, depth=2)
    arr = t.to_depth2_arrays()
    host = t.predict(x)
    dev = np.array([int(arr.predict(jnp.asarray(row))) for row in x[:200]])
    assert (dev == host[:200]).all()


def _walk(feat, thr, leaf, f):
    """Plain depth-2 walk: root, then the child the root's test picks."""
    node = 2 if f[feat[0]] >= thr[0] else 1
    right = f[feat[node]] >= thr[node]
    return int(leaf[(2 if node == 2 else 0) + int(right)])


@hypothesis.settings(max_examples=60, deadline=None)
@hypothesis.given(seed=st.integers(0, 2**31 - 1))
def test_dtree_predict_matches_plain_walk(seed):
    # feature values drawn from the thresholds themselves and +-inf, so
    # exact ties (`>=` at the threshold) are common; batched under vmap
    # as the simulator's engine runs it
    import jax
    rng = np.random.RandomState(seed)
    F, n = 7, 64
    feat = rng.randint(0, F, 3).astype(np.int32)
    thr = rng.choice([-1.5, 0.0, 2.0, 4.0, np.inf], 3).astype(np.float32)
    leaf = rng.randint(0, 2, 4).astype(np.int32)
    pool = np.concatenate([thr, [-np.inf, np.inf, -0.0, 3.0]])
    x = rng.choice(pool, (n, F)).astype(np.float32)
    tree = DTree(jnp.asarray(feat), jnp.asarray(thr), jnp.asarray(leaf))
    dev = np.asarray(jax.vmap(tree.predict)(jnp.asarray(x)))
    assert dev.tolist() == [_walk(feat, thr, leaf, row) for row in x]


def test_lr_learns_linear_concept():
    rng = np.random.RandomState(1)
    x = rng.randn(3000, 3).astype(np.float32)
    y = (x @ np.array([1.0, -2.0, 0.5]) > 0).astype(np.int32)
    m = clf.LogisticRegression.fit(x, y, steps=300)
    assert m.accuracy(x, y) > 0.93
    assert m.storage_kb() == (3 + 1) * 4 / 1024.0


def test_greedy_select_finds_informative_features():
    x, y = _toy()
    sel = clf.greedy_select(x, y, k=2)
    assert set(sel) == {0, 2}


@hypothesis.settings(max_examples=10, deadline=None)
@hypothesis.given(seed=st.integers(0, 10_000))
def test_property_dt_predictions_binary(seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(200, 3).astype(np.float32)
    y = rng.randint(0, 2, 200).astype(np.int32)
    t = clf.DecisionTree.fit(x, y, depth=3)
    p = t.predict(x)
    assert set(np.unique(p)).issubset({0, 1})


def test_balanced_weighting_handles_skew():
    rng = np.random.RandomState(0)
    x = rng.randn(5000, 2).astype(np.float32)
    y = ((x[:, 0] > 1.5)).astype(np.int32)       # ~7% positives
    t = clf.DecisionTree.fit(x, y, depth=2)
    # recall of the minority class must be decent with balancing
    pred = t.predict(x)
    recall = (pred[y == 1] == 1).mean()
    assert recall > 0.8
