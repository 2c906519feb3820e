"""Per-architecture smoke tests on REDUCED configs (same family wiring,
tiny dims): one forward + one train-grad step asserting shapes and
finiteness, and prefill->decode vs full-forward consistency.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES
from repro.configs.registry import ARCH_IDS, cells, get_config, input_specs, smoke_config
from repro.models import model as M

jax.config.update("jax_platform_name", "cpu")

B, S = 2, 64


def _inputs(cfg, key, b=B, s=S):
    if cfg.input_mode == "embeddings":
        return jax.random.normal(key, (b, s, cfg.d_model), cfg.adtype) * 0.1
    return jax.random.randint(key, (b, s), 0, cfg.vocab)


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_grad_step(arch, rng):
    cfg = smoke_config(get_config(arch))
    params = M.init_model(rng, cfg)
    inputs = _inputs(cfg, jax.random.fold_in(rng, 1))
    labels = jax.random.randint(jax.random.fold_in(rng, 2), (B, S), 0, cfg.vocab)

    logits, _, aux, _ = M.forward(cfg, params, inputs)
    assert logits.shape == (B, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    (loss, metrics), grads = jax.value_and_grad(
        lambda p: M.lm_loss(cfg, p, inputs, labels), has_aux=True)(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in flat)
    # a plain SGD step must keep the model finite
    params2 = jax.tree.map(lambda p, g: p - 0.01 * g.astype(p.dtype), params, grads)
    logits2, _, _, _ = M.forward(cfg, params2, inputs)
    assert np.isfinite(np.asarray(logits2, np.float32)).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch, rng):
    """decode(prefill(x[:s]), x[s]) must match forward(x[:s+1])[-1]."""
    import dataclasses
    cfg = smoke_config(get_config(arch))
    if cfg.moe.n_experts:
        # capacity drops depend on how many tokens compete for an expert's
        # slots, which differs by construction between full-forward and
        # single-token decode; give every token a slot for this check.
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.n_experts) / cfg.moe.top_k))
    params = M.init_model(rng, cfg)
    # chunked scans need chunk-aligned S; pad the reference to 2*S and read
    # position S — causality makes trailing padding invisible at S.
    full = _inputs(cfg, jax.random.fold_in(rng, 3), B, 2 * S)

    ref_logits, _, _, _ = M.forward(cfg, params, full)
    want = np.asarray(ref_logits[:, S], np.float32)

    prefix = full[:, :S]
    last = full[:, S:][:, :1]
    _, cache, _, _ = M.forward(cfg, params, prefix, collect_cache=True)
    cache = M.pad_cache(cfg, cache, S + 1)
    got_logits, new_cache = M.decode(cfg, params, cache, last, serve=False)
    got = np.asarray(got_logits, np.float32)

    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    assert (np.asarray(new_cache["pos"]) == S + 1).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_input_specs_cover_cells(arch):
    """Every assigned cell has well-formed ShapeDtypeStruct inputs."""
    cfg = get_config(arch)
    for sh in cells(arch):
        specs = input_specs(cfg, sh)
        assert "inputs" in specs
        leaves = jax.tree.leaves(specs)
        assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
        if sh.kind == "decode":
            assert "cache" in specs


def test_long500k_skips_match_design():
    ran = {a for a in ARCH_IDS
           if any(c.name == "long_500k" for c in cells(a))}
    assert ran == {"xlstm-1.3b", "zamba2-2.7b", "mixtral-8x7b"}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reset_slot_restores_the_init_values(arch):
    """Resetting every slot of a used decode cache gives back what
    ``init_cache`` makes (the sLSTM stabiliser's -1e30 included); resetting
    one leaves the others as they were."""
    cfg = smoke_config(get_config(arch))
    fresh = M.init_cache(cfg, 3, 16)
    used = jax.tree.map(lambda a: a * 2 + 1, fresh)
    one = M.reset_slot(cfg, used, 1)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(fresh)):
        assert (np.asarray(a) != np.asarray(b)).any()
    every = M.reset_slot(cfg, M.reset_slot(cfg, one, 0), 2)
    jax.tree.map(np.testing.assert_array_equal, every, fresh)
