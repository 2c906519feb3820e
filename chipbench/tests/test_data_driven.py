"""Every cell, mix, configuration, reference and metric is found by name:
a new one is new files and new entries in ``BENCHMARK.json``, with no
edit to a file that is there.  These tests add one of each in a scratch
directory and run the harness on them."""
import copy
import json
import time

import pytest

from chipbench import check, run, traffic
from chipbench.tests.helpers import PEAKS, tiny_conf, tiny_spec

COPY_REFERENCE = "from chipbench.reference import Reference  # noqa: F401\n"
# the dense reference with its logits moved one id along the vocabulary
PERMUTED_REFERENCE = """import numpy as np
from chipbench.reference import Reference as Dense


class Reference(Dense):
    def logit_stats(self, h, ids):
        v = self.m["vocab"]
        best, got, first = super().logit_stats(h, (np.asarray(ids) + 1) % v)
        return best, got, (np.asarray(first) - 1) % v
"""


def test_every_named_file_exists():
    bench = run.load_benchmark()
    for c in bench["configs"]:
        assert (run.ROOT / c["file"]).is_file()
        conf = run.load_config(c["name"])
        assert conf["name"] == c["name"]
        ref = run.reference_of(conf)
        assert (run.REFERENCE_DIR / f"{conf['reference']}.py").is_file()
        for method in ("routing", "hidden", "logit_stats"):
            assert callable(getattr(ref, method)), (c["name"], method)
    for w in bench["workloads"]:
        traffic.load(w["traffic"])
        run.load_config(w["config"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.reader(m["name"])), m["name"]
        for w in m.get("workloads", []):
            run.cell_of(bench, w)


def _scratch_cell(tmp_path, monkeypatch, reference: str, source: str):
    """A configuration, its reference, a mix and a metric as new files in
    a scratch directory, and a cell of them in a copy of the benchmark;
    returns the cell's result from ``run.measure``."""
    for d in ("configs", "traffic", "metrics", "references"):
        (tmp_path / d).mkdir()
    conf = dict(tiny_conf("mha-layernorm"), reference=reference)
    (tmp_path / "configs" / "mha-layernorm.json").write_text(json.dumps(conf))
    (tmp_path / "references" / f"{reference}.py").write_text(source)
    (tmp_path / "traffic" / "tiny_burst.json").write_text(
        json.dumps(dict(tiny_spec(), rate_per_s=9.0)))
    (tmp_path / "metrics" / "tokens_generated.py").write_text(
        "def read(ctx):\n    return ctx.served.window.generated\n")
    for name in ("setup_s", "ttft_p95_ms"):
        (tmp_path / "metrics" / f"{name}.py").write_text(
            (run.METRIC_DIR / f"{name}.py").read_text())
    monkeypatch.setattr(run, "CONFIG_DIR", tmp_path / "configs")
    monkeypatch.setattr(run, "METRIC_DIR", tmp_path / "metrics")
    monkeypatch.setattr(run, "REFERENCE_DIR", tmp_path / "references")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path / "traffic")

    bench = copy.deepcopy(run.load_benchmark())
    cell = {"name": "mha-layernorm.tiny_burst", "config": "mha-layernorm",
            "traffic": "tiny_burst", "chips": 1, "why": "test"}
    bench["workloads"].append(cell)
    bench["end_to_end"].append({"name": "tokens_generated",
                                "unit": "tokens", "better": "higher",
                                "bound": 0.1, "source": "host_clock",
                                "workloads": [cell["name"]]})
    next(m for m in bench["end_to_end"] if m["name"] == "ttft_p95_ms")[
        "workloads"].append(cell["name"])

    found = run.cell_of(bench, cell["name"])
    metrics = run.metrics_for(bench, cell["name"], trace=False)
    assert [m["name"] for m in metrics] == ["setup_s", "ttft_p95_ms",
                                            "tokens_generated"]
    return run.measure(run.load_config(found["config"]),
                       traffic.load(found["traffic"]), metrics, seed=5,
                       seconds=1.5, trace=False,
                       t_start=time.perf_counter(), peaks=PEAKS,
                       clock=run.CompileClock())


def test_a_new_cell_is_only_new_files(tmp_path, monkeypatch):
    res = _scratch_cell(tmp_path, monkeypatch, "dense_mcma_copy",
                        COPY_REFERENCE)
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"setup_s", "ttft_p95_ms",
                                   "tokens_generated"}
    assert res["metrics"]["tokens_generated"]["value"] > 0


def test_a_wrong_reference_file_makes_the_run_not_correct(tmp_path,
                                                          monkeypatch):
    """The reference a configuration names is the one that decides
    ``correct``: one whose logits are permuted fails the same run."""
    res = _scratch_cell(tmp_path, monkeypatch, "dense_mcma_permuted",
                        PERMUTED_REFERENCE)
    gap = res["check"]["max_logit_gap"]
    assert not res["correct"], res["check"]
    assert gap["value"] > gap["limit"], gap
    assert res["check"]["checked_tokens_at_least"]["value"] > 0


@pytest.mark.parametrize("name", [None, "no_such_reference", "../reference"])
def test_an_unknown_reference_is_refused_before_serving(name, monkeypatch):
    """A configuration that names no reference file, or one that is not
    there, stops the run before a server is built or weights are made."""
    from repro.runtime.server import DecodeServer

    def never(*_, **__):
        raise AssertionError("served before the reference was found")
    monkeypatch.setattr(DecodeServer, "__init__", never)
    monkeypatch.setattr(run, "make_params", never)
    conf = tiny_conf("internlm2-1.8b")
    if name is None:
        del conf["reference"]
    else:
        conf["reference"] = name
    with pytest.raises(SystemExit, match="names reference"):
        run.measure(conf, tiny_spec(), [], seed=5, seconds=1.0, trace=False,
                    t_start=time.perf_counter(), peaks=PEAKS,
                    clock=run.CompileClock())
    with pytest.raises(SystemExit, match="names reference"):
        check.check_served(conf, tiny_spec(), 5, served=None)


def test_model_config_builds_nested_sections_and_lists():
    """A JSON ``model`` section can state a nested dataclass and a tuple;
    the result is hashable, as jitted code keyed on it needs."""
    from repro.configs.base import SSMConfig
    conf = tiny_conf("internlm2-1.8b")
    conf["model"]["ssm"] = {"d_state": 128, "head_dim": 64, "chunk": 256,
                            "expand": 2}
    conf["approx"]["invoke_fracs"] = [0.3, 0.2, 0.1]
    cfg = run.model_config(conf)
    assert cfg.ssm == SSMConfig(d_state=128, head_dim=64, chunk=256,
                                expand=2)
    assert cfg.approx.invoke_fracs == (0.3, 0.2, 0.1)
    hash(cfg)
    assert run.model_config(copy.deepcopy(conf)) == cfg


def test_model_config_of_the_dense_cell_is_unchanged():
    """For a file of flat sections the result is the plain constructor's."""
    from repro.configs.base import ApproxConfig, ModelConfig
    conf = run.load_config("internlm2-1.8b")
    plain = ModelConfig(**conf["model"], approx=ApproxConfig(**conf["approx"]))
    cfg = run.model_config(conf)
    assert cfg == plain and hash(cfg) == hash(plain)


def test_without_a_tpu_no_result_and_a_failing_exit(tmp_path):
    """On this CPU the harness refuses to measure: exit 2, no JSON."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(run.ROOT / "chipbench" / "run.py"),
                        "--workload", "internlm2-1.8b.chat", "--seed",
                        str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=tmp_path)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
