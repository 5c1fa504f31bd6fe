"""The harness finds every piece by its name in BENCHMARK.json, and a new
piece is a new file: nothing existing is edited."""
import json
import shutil
import subprocess
import sys

import pytest

import tiny
from lib.registry import BENCH, Registry


def test_every_cell_of_the_benchmark_resolves():
    reg = Registry()
    spec = reg.spec
    for w in spec["workloads"]:
        assert reg.config(w["config"])["name"] == w["config"]
        t = reg.traffic(w["traffic"])
        assert hasattr(reg.driver(t["driver"]), "run")
        assert set(reg.cell(w["name"])["limits"])
    for m in spec["per_layer"]:
        assert callable(reg.metric(m["name"]).read)
    for c in spec["configs"]:
        cfg = reg.config(c["name"])
        assert hasattr(reg.reference(cfg["reference"]), "Round")
        assert hasattr(reg.model(cfg["model"]), "fed_round_flops")


def test_a_new_metric_file_is_found_with_nothing_edited(tmp_path):
    reg = tiny.registry(tmp_path, modules=("drivers", "references"))
    shutil.copytree(BENCH / "metrics", reg.bench / "metrics")
    (reg.bench / "metrics" / "steps_seen.py").write_text(
        "def read(record):\n    return record.get('steps')\n")
    spec = reg.spec
    spec["per_layer"].append({"name": "steps_seen", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "round_s",
                              "workloads": ["fed-tiny"]})
    (reg.bench.parent / "BENCHMARK.json").write_text(json.dumps(spec))
    assert [m["name"] for m in reg.per_layer("fed-tiny")] == ["steps_seen"]
    assert reg.metric("steps_seen").read({"steps": 7}) == 7
    assert reg.per_layer("serve-tiny") == []


def test_a_new_roofline_file_reads_its_kernel_with_nothing_edited(tmp_path):
    """A kernel's roofline is one file: it picks its calls among those the
    harness parsed from the programs the window drove, and counts their
    work."""
    from lib import trace as TR
    reg = tiny.registry(tmp_path, modules=("drivers", "references"))
    shutil.copytree(BENCH / "metrics", reg.bench / "metrics")
    (reg.bench / "metrics" / "decode_attention_roofline.py").write_text(
        "from lib import roofline\n\n\n"
        "def match(call):\n    return call['wrapper'] == 'decode_attention'\n"
        "\n\ndef work(call):\n    return {'flops': 2e11, 'bytes': 0}\n\n\n"
        "def read(record):\n    return roofline.share(record, match, work)\n")
    trace = TR.Trace(
        {"/device:TPU:0": [TR.Event("k.1", 1.0, 0.5, "jit_serve"),
                           TR.Event("m.1", 2.0, 1.0, "jit_serve")]},
        [TR.Event(TR.WINDOW_SPAN, 0.0, 5.0)])
    record = {"trace": trace,
              "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
              "programs": {"jit_serve": {
                  "k.1": {"wrapper": "decode_attention"},
                  "m.1": {"wrapper": "zo_dual_matmul"}}}}
    # 0.2 s of compute at the peak over 0.5 s on the device
    assert reg.metric("decode_attention_roofline").read(record) == \
        pytest.approx(40.0)


def test_missing_pieces_are_errors(tmp_path):
    reg = tiny.registry(tmp_path)
    with pytest.raises(KeyError):
        reg.workload("no-such-cell")
    with pytest.raises(FileNotFoundError):
        reg.traffic("no-such-mix")
    with pytest.raises(FileNotFoundError):
        reg.metric("no_such_metric")


def test_peaks_refuse_an_unknown_device():
    from lib import peaks
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "fed-gpt2m-s1024", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        cwd=str(BENCH.parent))
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
