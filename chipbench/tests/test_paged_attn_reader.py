"""The reader of ``decode_paged_attn_kernel_ms.steady`` on hand-built
events: the paged attention kernel's time per decode step run, counted
under ``attention`` by the scope split, and nothing for a program that
has no such kernel."""
import types

import pytest

from chipbench import run, scopes as S, trace as T
from repro import scopes as names

CHIP = "/device:TPU:0"
BODY = "jit(decode_step)/while/body/closed_call/"
KERNEL = BODY + f"attention/jit(paged_attention)/{names.PAGED_ATTENTION}/" \
    "pallas_call:"
METRIC = "decode_paged_attn_kernel_ms.steady"


def _summary(with_kernel=True):
    """A window [0, 10) with decode runs [1, 2) and [4, 5), a chunk run
    [6, 7), and a kernel operation outside any run."""
    ops, modules = [], []
    for base in (1.0, 4.0):
        ops += [S.Op(CHIP, "%scatter", BODY + "attention/kv_write/scatter:",
                     base, base + 0.01),
                S.Op(CHIP, "%dot", BODY + "attention/dot_general:",
                     base + 0.30, base + 0.35)]
        if with_kernel:
            ops.append(S.Op(CHIP, "%custom-call.1", KERNEL, base + 0.01,
                            base + 0.03))
        modules.append(S.Op(CHIP, f"{S.DECODE_PROGRAM}(1)", "", base,
                            base + 1.0))
    ops += [S.Op(CHIP, "%custom-call.2", KERNEL, 6.1, 6.5),
            S.Op(CHIP, "%custom-call.3", KERNEL, 8.0, 8.5)]
    modules.append(S.Op(CHIP, f"{S.CHUNK_PROGRAM}(2)", "", 6.0, 7.0))
    return S.ScopeSummary(ops, modules, [S.Span(T.WINDOW_SPAN, 0.0, 10.0)])


@pytest.fixture
def read(monkeypatch):
    def read(summary):
        monkeypatch.setattr(S, "summary", lambda ctx: summary)
        return run.reader(METRIC)(None)
    return read


def test_the_kernel_time_per_decode_run(read):
    assert read(_summary()) == pytest.approx(20.0)


def test_the_kernel_counts_under_attention_not_the_kv_scopes():
    split, n = _summary().step_split(S.DECODE_PROGRAM)
    assert n == 2
    assert split[names.ATTENTION] == pytest.approx(0.07)
    assert split[names.KV_WRITE] == pytest.approx(0.01)


def test_nothing_to_read_without_the_kernel(read, monkeypatch):
    assert read(_summary(with_kernel=False)) is None
    assert read(None) is None
    # a program that predates the kernel's name
    monkeypatch.setattr(S, "names", types.SimpleNamespace())
    assert read(_summary()) is None


def test_the_metric_is_declared_for_the_chat_cell():
    bench = run.load_benchmark()
    m = {m["name"]: m for m in bench["per_layer"]}[METRIC]
    assert m["workloads"] == ["internlm2-1.8b.chat"]
    assert m["moves"] == "itl_p95_ms" and m["layer"] == "paged KV cache"
