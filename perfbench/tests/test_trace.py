"""The trace reduction on the small trace kept beside it, against values
counted by hand from ``data/tiny.xplane.txt`` (the same trace, readable;
``data/tiny.xplane.pb`` is its binary form, in the profiler's own
format).  Times there are microseconds:

chip 0   jit_decode_burst [0,40): fusion [0,10) attention [10,25) . fusion [30,40)
         jit_prefill [60,100): fusion [60,80) while [80,100) { fusion [80,90) flash [90,100) }
         jit_decode_burst [130,160): fusion [130,140) attention [140,160)
chip 1   jit_decode_burst [5,45): fusion [5,15) all-reduce [15,45)
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import peaks  # noqa: E402
import xtrace  # noqa: E402

US = 1e-6


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "reduced.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, os.path.join(os.path.dirname(HERE), "xtrace.py"),
                    os.path.join(HERE, "data", "tiny.xplane.pb"), str(out)],
                   check=True, env=env, timeout=120)
    return json.loads(out.read_text())


def test_busy_union_and_idle_share(reduced):
    assert reduced["chips"] == 2
    assert reduced["window_s"] == pytest.approx(160 * US)
    assert reduced["busy_s_by_chip"]["0"] == pytest.approx(105 * US)
    assert reduced["busy_s_by_chip"]["1"] == pytest.approx(40 * US)
    assert reduced["busy_s"] == pytest.approx(72.5 * US)
    assert reduced["idle_pct_worst"] == pytest.approx(75.0)


def test_time_by_name_counts_leaves_only(reduced):
    ops = reduced["ops"]
    assert ops["jit_decode_burst/fusion"] == pytest.approx(40 * US)
    assert ops["jit_decode_burst/ragged_paged_attention_kvsplit"] == pytest.approx(35 * US)
    assert ops["jit_prefill/fusion"] == pytest.approx(30 * US)
    assert ops["jit_prefill/flash_attention"] == pytest.approx(10 * US)
    assert ops["jit_decode_burst/all-reduce"] == pytest.approx(30 * US)
    assert "jit_prefill/while" not in ops  # its body carries the time
    assert sum(ops.values()) == pytest.approx(145 * US)


def test_gaps_are_named_by_what_runs_next(reduced):
    gaps = reduced["gaps"]
    assert gaps["inside:jit_decode_burst"] == pytest.approx(5 * US)
    assert gaps["before:jit_prefill"] == pytest.approx(20 * US)
    assert gaps["before:jit_decode_burst"] == pytest.approx(35 * US)
    assert gaps["after:last_op"] == pytest.approx(115 * US)
    # every chip's busy + gaps is the window
    assert sum(gaps.values()) + 145 * US == pytest.approx(2 * 160 * US)


def test_names():
    assert xtrace.base_name("%fusion.12 = bf16[8,128] fusion(...)") == "fusion"
    assert xtrace.base_name("all-reduce.4") == "all-reduce"
    assert xtrace.module_name("jit_decode_burst(1234)") == "jit_decode_burst"
    assert xtrace.top({"a": 1.0, "b": 3.0}, 1) == [["b", 3.0]]


def test_peaks_table_raises_on_an_unknown_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
