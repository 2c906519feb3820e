"""The Mamba2 mixer's readers on hand-built events (the device time of
its scopes per step run, and the state update's share of the HBM
roofline with its bytes counted from the active rows), and the
granite-4.0-h-micro configuration served through the harness at a small
size against its reference."""
import copy
import time
import types

import numpy as np
import pytest

from chipbench import run, scopes as S, ssm, trace as T
from chipbench.serve import Tick
from chipbench.tests.helpers import PEAKS, tiny_spec
from repro import scopes as names

CHIP = "/device:TPU:0"
DECODE = "jit(decode_step)/while/body/closed_call/"
CHUNK = "jit(prefill_chunk_step)/while/body/"
MIXER = f"{names.SSM_MIXER}/"
STATE = f"{names.SSM_MIXER}/{names.SSM_STATE}/"
GRANITE = "granite-4.0-h-micro"


def _summary():
    """A window [0, 10): decode runs [1, 2) and [4, 5), whose state ops
    take 10 and 20 ms and whose other mixer ops 5 ms; a chunk run [6, 7)
    with 30 ms of mixer and 10 ms of state ops; an op outside any run."""
    ops, modules = [], []
    for base, state_s in ((1.0, 0.010), (4.0, 0.020)):
        ops += [S.Op(CHIP, "%fusion.1", DECODE + MIXER + "dot_general:",
                     base, base + 0.005),
                S.Op(CHIP, "%fusion.2", DECODE + STATE + "add:",
                     base + 0.01, base + 0.01 + state_s),
                S.Op(CHIP, "%fusion.3", DECODE + "exact_ffn/dot:",
                     base + 0.1, base + 0.2)]
        modules.append(S.Op(CHIP, f"{S.DECODE_PROGRAM}(1)", "", base,
                            base + 1.0))
    ops += [S.Op(CHIP, "%fusion.4", CHUNK + MIXER + "dot:", 6.0, 6.03),
            S.Op(CHIP, "%fusion.5", CHUNK + STATE + "add:", 6.1, 6.11),
            S.Op(CHIP, "%fusion.6", DECODE + STATE + "add:", 8.0, 8.5)]
    modules.append(S.Op(CHIP, f"{S.CHUNK_PROGRAM}(2)", "", 6.0, 7.0))
    return S.ScopeSummary(ops, modules, [S.Span(T.WINDOW_SPAN, 0.0, 10.0)])


def _ctx(actives=(16, 8)):
    """What the readers read: the granite configuration, the peaks, and
    the traced stretch's recorded ticks (a chunk call between the two
    decode calls, and a decode call before the stretch)."""
    ticks = [Tick("decode", [], None, np.ones(32, bool), None, None)]
    for i, n in enumerate(actives):
        rows = np.arange(32) < n
        ticks.append(Tick("decode", [], None, rows, None, None))
        if i == 0:
            ticks.append(Tick("chunk", [], None, np.full(32, 9), None, None))
    served = types.SimpleNamespace(ticks=ticks, trace_ticks=(1, len(ticks)))
    return types.SimpleNamespace(conf=run.load_config(GRANITE), peaks=PEAKS,
                                 served=served)


@pytest.fixture
def read(monkeypatch):
    def read(metric, summary, ctx=None):
        monkeypatch.setattr(S, "summary", lambda ctx: summary)
        return run.reader(metric)(ctx or _ctx())
    return read


def test_the_mixer_and_state_times_per_run(read):
    assert read("decode_ssm_mixer_ms.steady", _summary()) \
        == pytest.approx(5.0)
    assert read("decode_ssm_state_ms.steady", _summary()) \
        == pytest.approx(15.0)
    assert read("prefill_ssm_ms.steady", _summary()) == pytest.approx(40.0)


def test_the_state_bytes_of_a_slot():
    model = run.load_config(GRANITE)["model"]
    # 36 Mamba2 layers x 64 heads x 64 x 128 state x 4 bytes
    assert ssm.state_bytes_per_slot(model) == 36 * 64 * 64 * 128 * 4
    assert ssm.state_bytes_per_slot(
        run.load_config("internlm2-1.8b")["model"] | {"ssm": {
            "expand": 2, "d_state": 128}}) == 0


def test_the_roofline_counts_the_active_rows(read):
    """Run 1: 16 active slots in 10 ms of state ops; run 2: 8 in 20 ms.
    Each moves its active slots' state once in and once out."""
    per_slot = 36 * 64 * 64 * 128 * 4
    want = [2 * 16 * per_slot / 0.010 / PEAKS["hbm_bytes_per_s"],
            2 * 8 * per_slot / 0.020 / PEAKS["hbm_bytes_per_s"]]
    got = read("ssm_state_roofline.steady", _summary())
    assert got == pytest.approx(100.0 * sum(want) / 2)
    assert 0 < got < 100


def test_nothing_to_read_without_the_names(read, monkeypatch):
    for metric in ("decode_ssm_mixer_ms.steady", "decode_ssm_state_ms.steady",
                   "prefill_ssm_ms.steady", "ssm_state_roofline.steady"):
        assert read(metric, None) is None
    # a program that predates the mixer's scopes
    monkeypatch.setattr(S, "names", types.SimpleNamespace(
        DEVICE_SCOPES=(), SWITCH_KERNELS=()))
    for metric in ("decode_ssm_mixer_ms.steady", "decode_ssm_state_ms.steady",
                   "prefill_ssm_ms.steady", "ssm_state_roofline.steady"):
        assert read(metric, _summary()) is None


def test_the_metrics_are_declared_for_the_granite_cell():
    bench = run.load_benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in ("decode_ssm_mixer_ms.steady", "decode_ssm_state_ms.steady",
                 "prefill_ssm_ms.steady", "ssm_state_roofline.steady"):
        m = per_layer[name]
        assert m["workloads"] == [f"{GRANITE}.chat"] and m["layer"] == \
            "SSM mixer"
        assert callable(run.reader(name))
    assert per_layer["ssm_state_roofline.steady"]["unit"] == "%"


def _tiny_granite():
    """The cell's configuration at a small size, with the norm epsilon
    set to the program's own 1e-6 (as ``helpers.tiny_conf`` does)."""
    conf = copy.deepcopy(run.load_config(GRANITE))
    conf["model"].update(n_layers=6, d_model=128, n_heads=4, n_kv_heads=2,
                         head_dim=64, d_ff=256, vocab=512,
                         layer_types=["mamba", "mamba", "attention"] * 2,
                         attention_multiplier=1 / 64)
    conf["model"]["ssm"].update(d_state=16, head_dim=32, chunk=16)
    conf["approx"].update(d_hidden=128)
    conf["serve"].update(batch=4, max_len=128, prefill_chunk=16)
    conf["rms_norm_eps"] = 1e-6
    return conf


def test_granite_served_through_the_harness_matches_its_reference():
    res = run.measure(_tiny_granite(), tiny_spec(), [], seed=2 ** 31 + 16,
                      seconds=2.0, trace=False, t_start=time.perf_counter(),
                      peaks=PEAKS, clock=run.CompileClock())
    nums = {k: v["value"] for k, v in res["check"].items()}
    assert res["correct"], nums
    assert nums["checked_tokens_at_least"] >= 10
    assert nums["plan_mismatch_ticks"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
