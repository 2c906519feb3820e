"""The layered family (granite-4.0-h: per-layer Mamba2 or NoPE attention
mixers, each layer with its own MCMA FFN) against the plain reference
``chipbench/references/granite_hybrid_mcma.py``, on the CPU at a small
size, with seeded random weights, comparing logits, not tokens.

The model is 6 layers ``[mamba, mamba, attention] x 2`` at d_model 64: two
Mamba2 heads of 32 with a state of 16 and a conv of 4 (with bias), and
attention of 4 query and 2 KV heads of 64, whose pages pack the two KV
heads into one row of 128 lanes.  Parameters and activations are float32
and the reference takes the program's norm epsilon, so the two differ
only in the order of float32 sums: the chunked SSD against the
position-by-position recurrence, online softmax over blocks against one
softmax.  That puts their logits within ``TOL`` = 1e-4 of the largest
logit (they measure 4.5e-7 to 5.1e-7 apart); keeping the Mamba2 SSD state
in bfloat16 instead puts them 3.5e-4 apart
(``test_a_bfloat16_state_fails_the_tolerance``), so ``TOL`` catches the
state kept one precision down.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench.references.granite_hybrid_mcma import Reference
from repro.models import layers
from repro.models import model as M
from repro.runtime.options import ServeOptions
from repro.runtime.server import DecodeServer, Request

jax.config.update("jax_platform_name", "cpu")

TOL = 1e-4
TYPES = ["mamba", "mamba", "attention", "mamba", "mamba", "attention"]
SEED = 2 ** 31 + 1616


def _conf(**approx):
    conf = copy.deepcopy(run.load_config("granite-4.0-h-micro"))
    conf["model"].update(n_layers=6, d_model=64, n_heads=4, n_kv_heads=2,
                         head_dim=64, d_ff=128, vocab=256, layer_types=TYPES,
                         attention_multiplier=1 / 64,
                         param_dtype="float32", act_dtype="float32")
    conf["model"]["ssm"].update(d_state=16, expand=1, head_dim=32, chunk=4)
    conf["approx"].update(d_hidden=32, **approx)
    conf["rms_norm_eps"] = 1e-6          # the program's own epsilon
    return conf


@pytest.fixture(scope="module")
def model():
    """(conf, cfg, params, reference) with no row over capacity."""
    conf = _conf(exact_frac=1.0, invoke_frac=1.0)
    cfg = run.model_config(conf)
    key = run.model_key(SEED)
    return conf, cfg, M.init_model(key, cfg), Reference(conf, key)


def _ref_logits(ref, tokens, modes):
    h = np.asarray(ref.hidden(tokens, modes), np.float64)
    w = np.asarray(ref.tables["tok"], np.float64).T / 8.0
    return h @ w


def _classes(cfg, params, tokens):
    """The tick router's class of each token, as the float32 program
    takes it."""
    x = layers.embed_fwd(cfg, params["embed"], jnp.asarray(tokens))
    return np.asarray(jnp.argmax(x @ params["tick_router"], -1))


def _rel(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / np.max(np.abs(want)))


def test_the_reference_draws_the_models_weights(model):
    conf, cfg, params, ref = model
    np.testing.assert_allclose(ref.tables["tok"], params["embed"]["tok"],
                               rtol=1e-6)
    np.testing.assert_allclose(ref.tables["router"], params["tick_router"],
                               rtol=1e-6)
    kinds = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(TYPES):
        w = ref.layer_weights(i)
        mix = jax.tree.map(lambda a: a[kinds[kind]], params[kind])
        kinds[kind] += 1
        ffn = jax.tree.map(lambda a: a[i], params["ffn"])["approx"]
        core = mix["core"] if kind == "mamba" else mix["attn"]
        names = (("w_xz", "w_bc", "w_dt", "w_out", "conv_w", "conv_b",
                  "dt_bias", "a_log") if kind == "mamba"
                 else ("wq", "wk", "wv", "wo"))
        for k in names:
            np.testing.assert_allclose(w[k], core[k], rtol=1e-6, err_msg=k)
        np.testing.assert_allclose(w["w_out_ffn"], ffn["ffn"]["w_out"],
                                   rtol=1e-6)
        np.testing.assert_allclose(w["a_w1"], ffn["a_w1"][:3, :64, :32],
                                   rtol=1e-6)


@pytest.mark.parametrize("mcma", ["exact", "modes"])
def test_forward_matches_the_reference(model, mcma):
    """All rows exact (the training forward), and the served forward with
    the tick router's classes and capacities that bind."""
    conf, cfg, params, ref = model
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                           0, cfg.vocab))
    if mcma == "exact":
        logits, *_ = M.forward(cfg, params, jnp.asarray(tokens))
        modes = np.zeros_like(tokens)
    else:
        from chipbench import check
        approx = dict(conf["approx"], exact_frac=0.5, invoke_frac=0.25)
        cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
            cfg.approx, exact_frac=0.5, invoke_frac=0.25, route_scope="tick"))
        logits, *_ = M.forward(cfg, params, jnp.asarray(tokens), serve=True)
        mode, _, _ = check.plan(_classes(cfg, params, tokens.reshape(-1)),
                                tokens.size, 3, approx)
        assert (mode == check.DROPPED).any() and (mode > 0).any()
        modes = mode.reshape(tokens.shape)
    assert _rel(logits, _ref_logits(ref, tokens, modes)) < TOL


def _serve(cfg, params, prompts, backend, *, batch=3, max_new=6):
    """Serve ``prompts`` through the paged, chunked server; returns the
    requests and, per request, the logits of each decode tick that
    sampled one of its tokens."""
    srv = DecodeServer(cfg, params, options=ServeOptions(
        batch=batch, max_len=64, use_mcma_dispatch=True, route_scope="tick",
        kv_page_size=4, prefill_chunk=8, backend=backend, admission="fifo"))
    logits = {}
    step = srv.decode

    def recorded(params, cache, tokens, rows, *rest, **kw):
        out = step(params, cache, tokens, rows, *rest, **kw)
        for i in np.nonzero(np.asarray(rows))[0]:
            logits.setdefault(srv.slots[i].rid, []).append(
                np.asarray(out[0][i]))
        return out
    srv.decode = recorded
    reqs = [Request(rid=i, prompt=np.asarray(p, np.int32), max_new=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained(500)
    assert all(r.done and not r.aborted for r in reqs)
    return srv, reqs, logits


def _check_against_reference(cfg, params, ref, reqs, logits):
    worst = 0.0
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        want = _ref_logits(ref, seq[None],
                           _classes(cfg, params, seq)[None])[0]
        got = np.stack(logits[r.rid])
        assert got.shape[0] == len(r.out)
        worst = max(worst, _rel(got, want[len(r.prompt) - 1:]))
    return worst


def _prompts(vocab):
    rng = np.random.default_rng(7)
    # shorter than, as long as and longer than a chunk of 8
    return [rng.integers(0, vocab, n) for n in (5, 8, 19)]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_served_logits_match_the_reference(model, backend):
    """Chunked prefill, then decode through the paged cache (the packed
    paged kernel under ``pallas``, interpreted), against the reference's
    full forward pass, for ragged prompts in one batch."""
    conf, cfg, params, ref = model
    srv, reqs, logits = _serve(cfg, params, _prompts(cfg.vocab), backend)
    assert srv.prefill_ticks > 0
    assert _check_against_reference(cfg, params, ref, reqs, logits) < TOL


def test_the_server_chunks_and_pages_the_layered_family(model):
    _, cfg, params, _ = model
    srv = DecodeServer(cfg, params, options=ServeOptions(
        batch=2, max_len=32, kv_page_size=4, prefill_chunk=8))
    assert srv.chunkable and srv.prefill_chunk == 8
    assert srv.cache["k"].shape == (2, srv.n_pages, 4,
                                    *layers.pool_rows(cfg))
    assert layers.pool_rows(cfg) == (1, 128)
    assert srv.cache["mamba"]["h"].shape == (4, 2, 2, 32, 16)
    assert srv.cache["mamba"]["conv"].shape == (4, 2, 3, 64 + 2 * 16)


def test_a_refilled_slot_serves_what_a_fresh_server_serves(model):
    """One slot: a first request finishes, leaving its state and pages
    behind, and the next request in that slot gets the logits a fresh
    server gives it."""
    _, cfg, params, _ = model
    first, second = _prompts(cfg.vocab)[2], _prompts(cfg.vocab)[0]
    _, reqs, used = _serve(cfg, params, [first, second], "pallas", batch=1)
    _, fresh, alone = _serve(cfg, params, [second], "pallas", batch=1)
    assert reqs[1].out == fresh[0].out
    np.testing.assert_array_equal(np.stack(used[1]), np.stack(alone[0]))


def test_reset_zeroes_one_slots_state(model):
    _, cfg, _, _ = model
    cache = M.init_cache(cfg, 3, 32, page_size=4, kv_pages=24)
    cache = jax.tree.map(lambda a: a + 1, cache)
    out = M.reset_slot(cfg, cache, 1)
    for leaf in jax.tree.leaves(out["mamba"]):
        assert not np.asarray(leaf[:, 1]).any()
        assert (np.asarray(leaf[:, [0, 2]]) == 1).all()
    assert (np.asarray(out["block_table"][1]) == -1).all()
    np.testing.assert_array_equal(out["k"], cache["k"])


def test_a_short_chunk_advances_the_state_over_its_valid_tokens(model):
    """A chunk whose n_valid is below its length leaves each Mamba2 layer's
    SSD and conv state as feeding the valid tokens one at a time does,
    and a row with n_valid 0 (or masked) keeps its state exactly."""
    _, cfg, params, _ = model
    tokens = jax.random.randint(jax.random.PRNGKey(3), (3, 8), 0, cfg.vocab)
    start = M.init_cache(cfg, 3, 32)
    start, _ = M.decode_chunk(cfg, params, start, tokens, jnp.array([8, 8, 8]),
                              serve=False)                 # some history
    n_valid = jnp.array([3, 0, 8])
    chunked, _ = M.decode_chunk(cfg, params, start, tokens[:, ::-1],
                                n_valid, serve=False)
    fed = start
    for t in range(8):
        mask = t < n_valid
        _, fed = M.decode(cfg, params, fed, tokens[:, ::-1][:, t:t + 1],
                          serve=False, row_mask=mask)
    np.testing.assert_array_equal(chunked["pos"], fed["pos"])
    for name in ("h", "conv"):
        a, b = chunked["mamba"][name], fed["mamba"][name]
        assert _rel(a, np.asarray(b, np.float64)) < 1e-5, name
        np.testing.assert_array_equal(a[:, 1], start["mamba"][name][:, 1])


def _decode_logits(cfg, params, tokens, cache):
    out = []
    for t in range(tokens.shape[1]):
        lg, cache = M.decode(cfg, params, cache, jnp.asarray(tokens[:, t:t + 1]),
                             serve=False)
        out.append(lg)
    return np.stack(out, 1)


def test_a_bfloat16_state_fails_the_tolerance(model):
    """The same decode through the cache, once with the SSD state in
    float32 (within ``TOL``) and once kept in bfloat16 (outside it)."""
    _, cfg, params, ref = model
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (2, 48), 0,
                                           cfg.vocab))
    want = _ref_logits(ref, tokens, np.zeros_like(tokens))
    cache = M.init_cache(cfg, 2, 64)
    assert _rel(_decode_logits(cfg, params, tokens, cache), want) < TOL
    low = dict(cache, mamba=dict(
        cache["mamba"], h=cache["mamba"]["h"].astype(jnp.bfloat16)))
    assert _rel(_decode_logits(cfg, params, tokens, low), want) > TOL


def test_params_count_counts_each_mixer_and_ffn():
    cfg = run.model_config(run.load_config("granite-4.0-h-micro"))
    d, d_in, n, heads = 2048, 4096, 128, 64
    mamba = d * (2 * d_in + 2 * n + heads) + 5 * (d_in + 2 * n) + d_in * d
    attn = 2 * d * 2048 + 2 * d * 512
    ffn = 3 * d * 8192
    assert cfg.params_count() == 100352 * d + 36 * mamba + 4 * attn \
        + 40 * ffn
    assert 3.1e9 < cfg.params_count() < 3.25e9
