"""The plain reference against the served path, on the CPU at a small
size: the cell's configuration and an MHA/LayerNorm variant through the
paged, chunked ``DecodeServer`` with the ``pallas`` kernel in the
interpreter."""
import jax
import numpy as np
import pytest

from chipbench import check, run
from chipbench.reference import Reference
from chipbench.tests.helpers import measure, tiny_conf, tiny_spec

CONFIGS = ["internlm2-1.8b", "mha-layernorm"]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_draws_the_models_weights(name):
    """The reference makes its weights itself; they are the model's,
    bit for bit."""
    from repro.models import model as M
    conf = tiny_conf(name)
    key = run.model_key(2 ** 31 + 77)
    params = M.init_model(key, run.model_config(conf))
    ref = Reference(conf, key)
    t = ref.tables
    np.testing.assert_array_equal(t["tok"], params["embed"]["tok"])
    np.testing.assert_array_equal(t["unembed"], params["embed"]["unembed"])
    np.testing.assert_array_equal(t["router"], params["tick_router"])
    n = conf["approx"]["n_approx"]
    d, h = conf["model"]["d_model"], conf["approx"]["d_hidden"]
    for i in range(conf["model"]["n_layers"]):
        w = ref.layer_weights(i)
        blk = jax.tree.map(lambda a: a[i], params["blocks"])
        for k in ("wq", "wk", "wv", "wo"):
            np.testing.assert_array_equal(w[k], blk["attn"][k])
        for k in ("w_in", "w_out", "w_gate"):
            np.testing.assert_array_equal(w[k], blk["approx"]["ffn"][k])
        np.testing.assert_array_equal(w["a_w1"],
                                      blk["approx"]["a_w1"][:n, :d, :h])
        np.testing.assert_array_equal(w["a_w2"],
                                      blk["approx"]["a_w2"][:n, :h, :d])


@pytest.mark.parametrize("name", CONFIGS)
def test_served_tokens_match_the_reference(name):
    res = measure(tiny_conf(name), tiny_spec())
    nums = {k: v["value"] for k, v in res["check"].items()}
    assert res["correct"], nums
    assert nums["checked_tokens_at_least"] >= 10
    assert nums["plan_mismatch_ticks"] == 0
    assert nums["max_logit_gap"] <= 0.01
    assert res["attempted"] > 0 and res["failed"] == 0


def test_start_positions_follow_each_request():
    from chipbench.serve import Tick

    def tick(phase, rows, rids=(5, 6)):
        return Tick(phase, list(rids), None, np.asarray(rows), None, None)
    ticks = [tick("chunk", [3, 0]), tick("decode", [True, False]),
             tick("chunk", [0, 2]), tick("decode", [True, True]),
             # slot 0 takes a new request, which starts from position 0
             tick("decode", [True, True], rids=(7, 6))]
    got = [p.tolist() for p in check.start_positions(ticks)]
    assert got == [[0, 0], [3, 0], [4, 0], [4, 2], [0, 3]]


def test_replay_counts_ties_apart_from_faults():
    """A tick whose counts a router tie within rounding explains leaves
    its requests out of the sample; counts nothing explains are a
    mismatch."""
    approx = {"n_approx": 2, "exact_frac": 0.5, "invoke_frac": 0.5}
    table = np.array([0, 1, 2, 1])
    alt = np.array([1, 0, 0, 2])
    near = np.array([False, False, False, True])
    tok = np.array([[0], [1], [3], [2]], np.int32)

    def tick(counts, kept):
        from chipbench.serve import Tick
        return Tick("decode", [10, 11, 12, 13], tok, np.ones(4, bool),
                    np.asarray(counts, float), np.asarray(kept, float))
    # as the table says: classes 0, 1, 1, 2
    rep = check.replay([tick([1, 2, 1], [1, 2, 1])], (table, alt, near),
                       approx)
    assert (rep.ambiguous_ticks, rep.mismatches) == (0, 0)
    # token 3 tied between classes 1 and 2 and went to 2
    rep = check.replay([tick([1, 1, 2], [1, 1, 2])], (table, alt, near),
                       approx)
    assert (rep.ambiguous_ticks, rep.mismatches) == (1, 0)
    assert rep.ambiguous == {10, 11, 12, 13}
    # token 1 has no tie: counts that need it moved are a fault
    rep = check.replay([tick([2, 1, 1], [2, 1, 1])], (table, alt, near),
                       approx)
    assert (rep.ambiguous_ticks, rep.mismatches) == (0, 1)
