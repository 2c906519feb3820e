"""Paper-core behaviour + hypothesis property tests on system invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.apps import APPS, make_dataset
from repro.core import (npu_model, quality, train_iterative, train_mcca,
                        train_mcma, train_one_pass)
from repro.core.mcma import _labels_competitive, _labels_complementary
from repro.core.mlp import MLPSpec, balanced_weights

jax.config.update("jax_platform_name", "cpu")

FAST = dict(n_train=1_500, n_test=800, epochs=250)


@pytest.fixture(scope="module")
def bs_models():
    app = APPS["blackscholes"]
    key = jax.random.PRNGKey(0)
    xtr, ytr, xte, yte = make_dataset(app, key, FAST["n_train"], FAST["n_test"])
    ks = jax.random.split(key, 4)
    return app, xte, yte, {
        "one-pass": train_one_pass(app, ks[0], xtr, ytr, epochs=FAST["epochs"]),
        "iterative": train_iterative(app, ks[1], xtr, ytr, epochs=FAST["epochs"]),
        "mcma": train_mcma(app, ks[2], xtr, ytr, epochs=FAST["epochs"]),
        "mcca": train_mcca(app, ks[3], xtr, ytr, epochs=FAST["epochs"]),
    }


def test_all_methods_produce_valid_metrics(bs_models):
    app, xte, yte, models = bs_models
    for name, m in models.items():
        met = m.evaluate(xte, yte)
        assert 0.0 <= met.invocation <= 1.0, name
        assert 0.0 <= met.recall <= 1.0, name
        assert met.false_neg >= 0 and met.false_pos >= 0, name


def test_mcma_invocation_beats_one_pass(bs_models):
    """The paper's headline: MCMA recovers abandoned safe inputs."""
    app, xte, yte, models = bs_models
    inv_mcma = models["mcma"].evaluate(xte, yte).invocation
    inv_op = models["one-pass"].evaluate(xte, yte).invocation
    assert inv_mcma >= inv_op - 0.02, (inv_mcma, inv_op)


def test_npu_cost_model_monotone(bs_models):
    app, *_ = bs_models
    costs = [npu_model.cost(app, inv).time_per_call
             for inv in (0.0, 0.3, 0.6, 0.9)]
    assert all(a > b for a, b in zip(costs, costs[1:]))  # more inv = faster
    cpu = npu_model.cpu_only(app)
    full = npu_model.cost(app, 1.0)
    assert full.speedup_vs(cpu) < 1.0 or full.time_per_call < cpu.time_per_call


def test_mcca_consults_more_classifiers_than_mcma(bs_models):
    app, xte, yte, models = bs_models
    mcca = models["mcca"]
    if len(mcca.pairs) > 1:
        assert float(mcca.classifiers_consulted(xte)) > 1.0


# ---------------------------------------------------------------------------
# Hypothesis property tests
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(2, 5), st.integers(4, 60), st.floats(0.01, 0.5))
def test_complementary_labels_first_safe_wins(n_approx, n, bound):
    key = jax.random.PRNGKey(n_approx * 1000 + n)
    errs = jax.random.uniform(key, (n_approx, n), minval=0.0, maxval=1.0)
    labels = np.asarray(_labels_complementary(errs, bound))
    errs = np.asarray(errs)
    for j in range(n):
        safe = np.where(errs[:, j] <= bound)[0]
        assert labels[j] == (safe[0] if safe.size else n_approx)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 5), st.integers(4, 60), st.floats(0.01, 0.5))
def test_competitive_labels_argmin_under_bound(n_approx, n, bound):
    key = jax.random.PRNGKey(n_approx * 7919 + n)
    errs = jax.random.uniform(key, (n_approx, n), minval=0.0, maxval=1.0)
    labels = np.asarray(_labels_competitive(errs, bound))
    errs = np.asarray(errs)
    for j in range(n):
        if labels[j] < n_approx:
            assert errs[labels[j], j] <= bound
            assert labels[j] == np.argmin(errs[:, j])
        else:
            assert errs[:, j].min() > bound


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 6), st.integers(10, 200))
def test_balanced_weights_mean_one_and_class_balanced(n_classes, n):
    key = jax.random.PRNGKey(n_classes * 31 + n)
    labels = jax.random.randint(key, (n,), 0, n_classes)
    w = np.asarray(balanced_weights(labels, n_classes))
    assert w.shape == (n,)
    assert abs(w.mean() - 1.0) < 1e-4
    # every represented class contributes equal total weight
    labels = np.asarray(labels)
    sums = [w[labels == c].sum() for c in range(n_classes)
            if (labels == c).any()]
    np.testing.assert_allclose(sums, sums[0], rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(list(APPS)), st.integers(8, 64))
def test_per_sample_error_nonneg_and_zero_for_exact(app_name, n):
    app = APPS[app_name]
    key = jax.random.PRNGKey(n)
    x = app.gen(key, n)
    y = app.fn(x)
    err = np.asarray(quality.per_sample_error(app, y, y))
    assert (err >= 0).all() and err.max() < 1e-5


def test_mlp_spec_parse_and_macs():
    spec = MLPSpec.parse("6->8->1")
    assert spec.sizes == (6, 8, 1)
    assert spec.n_macs == 6 * 8 + 8 * 1
    assert spec.n_params == 6 * 8 + 8 + 8 * 1 + 1
