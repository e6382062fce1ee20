"""``hostgaps.py`` on the small trace kept beside it, against values
counted by hand from ``data/tiny_host.xplane.txt`` (``data/
tiny_host.xplane.pb`` is its binary form, made from it by
``jax.profiler.ProfileData.text_proto_to_serialized_xspace``).  Times
there are microseconds:

chip 0   jit_fused_step [0,30): fusion.1 [0,30) mlp
         jit_decode_burst [40,70): fusion.2 [40,50) attn_qkv, attention [50,65) attn, copy [65,70) no scope
         jit_decode_burst [100,130): while [100,130) { fusion.2 [100,110), attention [110,125), fusion.6 [125,130) sample }
         idle: [30,40) and [70,100), both before:jit_decode_burst
engine   step [2,72) { admit [3,8), prefill [8,20) { dispatch[fused_step] [10,18) },
thread          pack [20,72) { dispatch[decode_burst] [22,36), fetch[decode_burst] [36,68), emit [68,71) } }
         loop.publish [73,80)
         step [82,135) { admit [83,86), pack [86,134) { dispatch [88,99), fetch [99,128), emit [128,133) } }
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import hostgaps  # noqa: E402

US = 1e-6
DISPATCH, FETCH = "step.dispatch[decode_burst]", "step.fetch[decode_burst]"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    out = tmp_path_factory.mktemp("hostgaps") / "reduced.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(HERE), "hostgaps.py"),
         os.path.join(HERE, "data", "tiny_host.xplane.pb"), str(out)],
        check=True, env=env, timeout=120, capture_output=True, text=True)
    return json.loads(out.read_text()), done.stdout


def test_each_gap_goes_to_the_innermost_open_span(reduced):
    red, _ = reduced
    assert red["chips"] == 1 and red["window_s"] == pytest.approx(130 * US)
    assert set(red["idle_s_by_gap"]) == {"before:jit_decode_burst"}
    row = red["idle_s_by_gap"]["before:jit_decode_burst"]
    want = {DISPATCH: 6 + 11, FETCH: 4 + 1, "loop.publish": 7,
            "step.admit": 3, "step.pack": 1 + 2, "step.emit": 1, "step": 1,
            hostgaps.NO_SPAN: 1 + 2}
    assert set(row) == set(want)
    for span, us in want.items():
        assert row[span] == pytest.approx(us * US), span
    assert sum(row.values()) == pytest.approx(40 * US)  # the chip's idle time
    assert red["idle_s_by_span"] == pytest.approx(row)


def test_the_table_says_what_share_of_a_gap_is_named(reduced):
    _, text = reduced
    assert "before:jit_decode_burst: 0.000040 s, 92.5 % in spans" in text


def test_host_self_seconds_add_up(reduced):
    red, _ = reduced
    host = red["host_s_by_span"]
    assert host[FETCH] == pytest.approx((32 + 29) * US)
    assert host["step.dispatch[fused_step]"] == pytest.approx(8 * US)
    assert host["step.prefill"] == pytest.approx((12 - 8) * US)
    assert host["step.pack"] == pytest.approx((52 - 14 - 32 - 3 + 48 - 11 - 29 - 5) * US)
    assert host["step"] == pytest.approx((70 - 5 - 12 - 52 + 53 - 3 - 48) * US)
    # everything inside a top-level span, once: two steps and one publish
    assert sum(host.values()) == pytest.approx((70 + 7 + 53) * US)
    assert "DevicePutWithSharding" not in host  # not one of the program's spans


def test_device_seconds_by_named_scope_count_leaves_only(reduced):
    red, _ = reduced
    scope = red["device_s_by_scope"]
    assert scope["mlp"] == pytest.approx(30 * US)
    assert scope["attn_qkv"] == pytest.approx(20 * US)
    assert scope["attn"] == pytest.approx(30 * US)
    assert scope["sample"] == pytest.approx(5 * US)  # innermost; by ref_value
    assert scope[hostgaps.NO_SCOPE] == pytest.approx(5 * US)
    assert sum(scope.values()) == pytest.approx(90 * US)  # the while: its body


def test_segments_and_scope_names():
    seg = hostgaps.self_segments([(0, 100, "a"), (10, 60, "b"), (20, 35, "c"),
                                  (120, 130, "a")])
    assert seg == [(0, 10, "a"), (10, 20, "b"), (20, 35, "c"), (35, 60, "b"),
                   (60, 100, "a"), (120, 130, "a")]
    assert hostgaps.scope_of("jit(f)/while/body/attn/jit(k)/pallas_call:") == "attn"
    assert hostgaps.scope_of("jit(f)/sample/lm_head/dot_general:") == "lm_head"
    assert hostgaps.scope_of("jit(f)/while/body/add:") is None
    assert hostgaps.scope_of("") is None


def test_a_trace_without_a_host_plane_leaves_every_gap_unnamed():
    red = hostgaps.reduce_file(os.path.join(HERE, "data", "tiny.xplane.pb"))
    assert red["chips"] == 2 and red["device_s_by_scope"] == {}
    assert set(red["idle_s_by_span"]) == {hostgaps.NO_SPAN}
    assert red["idle_s_by_span"][hostgaps.NO_SPAN] == pytest.approx(175 * US)
