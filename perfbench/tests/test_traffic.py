"""The traffic generator, checked without a chip: every run of a mix is
offered one and the same schedule (for any two seeds the multiset of
lengths, their order, the count of arrivals and their instants are equal;
only the prompts' bytes differ), the rows are the lengths their file says
they were drawn from, and due-time accounting charges a stalled server's
delay to the requests it held up."""

import collections
import glob
import math
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from statistics import NormalDist

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import load  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402

MIXES = sorted(glob.glob(os.path.join(os.path.dirname(HERE), "traffic", "*.json")))
IDS = [os.path.basename(p) for p in MIXES]
SEEDS = (0, 7, 2**31 + 11, 2**32 + 5)


def lognormal_quantiles(n, d):
    """The ``n`` mid-quantiles ((i + 0.5) / n) of a log-normal, clipped:
    what a mix's ``drawn_from`` states."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return [int(min(d["max"], max(d["min"], round(
        d["median"] * math.exp(d["sigma"] * v))))) for v in z]


@pytest.mark.parametrize("path", MIXES, ids=IDS)
def test_every_seed_is_offered_the_same_schedule(path):
    mix = traffic.load(path)
    seconds = 51.0 if os.path.basename(path).startswith("chat") else 5.0
    s = traffic.schedule(mix, seconds, 16)
    # nothing of the schedule is drawn from --seed; the prompts' bytes are
    assert s == traffic.schedule(mix, seconds, 16)
    bytes_ = {traffic.prompt_for(seed, 0, 64) for seed in SEEDS}
    assert len(bytes_) == len(SEEDS), "the seed does not change the prompts"
    if mix["loop"] == "closed":
        assert s["clients"] == 16 * mix["clients_per_slot"] and not s["tail"]
        assert all(r["due"] is None for r in s["requests"])
        return
    d = [r["due"] for r in s["requests"]]
    assert d == sorted(d) and d[0] >= -s["ramp_s"] and d[-1] <= seconds
    counted = [x for x in d if x >= 0]
    # the offered rate is the file's (to a second's worth of arrivals),
    # in the window and in its halves
    assert len(counted) == pytest.approx(mix["rate_rps"] * seconds,
                                         abs=mix["rate_rps"])
    half = sum(1 for x in counted if x < seconds / 2)
    assert half == pytest.approx(len(counted) / 2, abs=3)
    t = [r["due"] for r in s["tail"]]
    assert t and t[0] > seconds and t[-1] <= seconds + mix["tail_max_s"]
    assert [r["i"] for r in s["requests"] + s["tail"]] == list(
        range(s["requests"][0]["i"], s["tail"][-1]["i"] + 1))


def test_the_cell_counts_148_arrivals():
    mix = traffic.load(traffic.traffic_path(
        os.path.dirname(os.path.dirname(HERE)), "chat-poisson"))
    s = traffic.schedule(mix, 51.0, 16)
    assert sum(1 for r in s["requests"] if r["due"] >= 0) == 148
    # the sweep's other rates: the same rows, their instants squeezed
    fast = traffic.schedule(mix, 30.0, 16, stretch=2.9 / 3.6)
    n = sum(1 for r in fast["requests"] if r["due"] >= 0)
    assert n == pytest.approx(3.6 * 30, abs=4)
    with pytest.raises(ValueError, match="rows end"):
        traffic.schedule(mix, 30.0, 16, stretch=0.2)


@pytest.mark.parametrize("path", MIXES, ids=IDS)
def test_rows_are_the_lengths_their_file_says(path):
    mix = traffic.load(path)
    g = mix["drawn_from"]
    grid_p = collections.Counter(lognormal_quantiles(g["n"], g["prompt"]))
    grid_o = collections.Counter(lognormal_quantiles(g["n"], g["output"]))
    rows = [r[-2:] for r in mix["requests"]]
    got_p = collections.Counter(p for p, _ in rows)
    got_o = collections.Counter(o for _, o in rows)
    cycles = -(-len(rows) // g["n"])
    if len(rows) % g["n"] == 0:  # whole cycles: exactly the grid, each time
        assert got_p == collections.Counter({k: v * cycles for k, v in grid_p.items()})
        assert got_o == collections.Counter({k: v * cycles for k, v in grid_o.items()})
    else:                        # a cut: no length more often than the grid
        assert all(got_p[k] <= grid_p[k] * cycles for k in got_p)
        assert all(got_o[k] <= grid_o[k] * cycles for k in got_o)
    if os.path.basename(path).startswith("chat"):  # ISSUE 24's grid
        assert (g["n"], min(grid_p), max(grid_p)) == (256, 32, 3072)
        assert min(grid_o) >= 16 and max(grid_o) <= 768
        assert max(p + o for p, o in rows) <= 4096 - 8
        assert 520 < sum(p for p, _ in rows) / len(rows) < 600
        assert 180 < sum(o for _, o in rows) / len(rows) < 200
        # any stretch of the schedule is spread over the quantiles
        head = sorted(p for p, _ in rows[:32])
        assert head[0] < 150 and head[-1] > 1500


def test_prompt_is_exactly_its_tokens_and_unique():
    a = traffic.prompt_for(5, 0, 300)
    b = traffic.prompt_for(5, 1, 300)
    assert len(traffic.token_ids(a)) == 300 and a != b
    assert traffic.token_ids(a)[0] == 1 and all(3 <= t < 259 for t in traffic.token_ids(a)[1:])
    assert len(load.request_body("m", a, 8)) > 300


class _StallingServer(BaseHTTPRequestHandler):
    """Answers every completion with two tokens, one at a time in the
    order of arrival, and sleeps 0.5 s before the first."""

    lock = threading.Lock()
    first = True

    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        with _StallingServer.lock:  # one at a time: a queue
            if _StallingServer.first:
                _StallingServer.first = False
                time.sleep(0.5)
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.end_headers()
            for tok in (7, 8):
                self.wfile.write(b'data: {"choices": [{"text": "", "token_id": %d}]}\n\n' % tok)
                self.wfile.flush()
            self.wfile.write(b"data: [DONE]\n\n")

    def log_message(self, *args):
        pass


def test_due_time_accounting_charges_the_stall_to_later_requests():
    _StallingServer.first = True
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _StallingServer)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        base = f"http://127.0.0.1:{httpd.server_port}"
        reqs = [{"i": i, "due": 0.05 * i, "prompt_len": 8, "max_tokens": 2}
                for i in range(4)]
        loop = load.OpenLoop(base, "m", 1, reqs, [], time.monotonic() + 0.1)
        loop.start()
        loop.wait_counted(loop.records, time.monotonic() + 10.0)
        loop.cut(timeout=5.0)
    finally:
        httpd.shutdown()
        httpd.server_close()
    assert all(r.ok and r.tokens == [7, 8] for r in loop.records)
    ttft = [stats.ttft_ms(r) for r in loop.records]
    # the server stalled 500 ms on the first request only; the three
    # behind it were due 50, 100, 150 ms later and waited out the rest
    assert ttft[0] >= 500
    assert ttft[1] >= 430 and ttft[2] >= 380 and ttft[3] >= 330
    # from the send time the same requests would look fast: that is the
    # error the due time avoids
    assert all((r.sent - r.due) * 1e3 < 50 for r in loop.records)


def test_tokens_are_counted_by_arrival_inside_the_window():
    rec = load.Record({"i": 0, "prompt_len": 4, "max_tokens": 4}, None)
    rec.stamps = [9.9, 10.0, 10.5, 11.01]
    assert stats.tokens_in([rec], 10.0, 11.0) == 2
    assert stats.tpot_ms(rec) == pytest.approx((11.01 - 9.9) / 3 * 1e3)
    assert stats.percentile([1.0, 2.0, 3.0, float("inf")], 90) == float("inf")
    assert stats.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
