"""The generator: lengths and order repeat for a seed and differ across
seeds, and every seed does the same amount of work."""

import json
import os

import numpy as np
import pytest

from chipbench import cells, traffic

SERVE = cells.load_workload("gpt2-large.serve.chat-closed")[1]["serve"]["traffic"]
TRAIN = cells.load_workload("gpt2-large.train.s1024")[1]["train"]["data"]
BIG = 3_000_000_019  # more than 32 signed bits hold


def test_request_plan_repeats_and_differs():
    a, b, c = (traffic.request_plan(SERVE, s) for s in (BIG, BIG, 7))
    assert a == b and a != c
    assert sorted(a) == sorted(c)  # same multiset of sizes, another order
    assert len(a) == SERVE["pool"]


def test_request_plan_follows_the_stated_distribution():
    plan = traffic.request_plan(SERVE, 1)
    prompts, outputs = zip(*plan)
    assert min(prompts) >= 16 and max(prompts) <= 512
    assert min(outputs) >= 32 and max(outputs) <= 384
    assert abs(np.median(prompts) - 96) <= 2 and abs(np.median(outputs) - 160) <= 2
    assert all(p + o <= SERVE["max_total"] for p, o in plan)
    assert abs(np.corrcoef(prompts, outputs)[0, 1]) < 0.15


def test_prompt_tokens_repeat_and_share_no_prefix():
    a = traffic.prompt_tokens(BIG, 5, 64, 50257)
    assert a == traffic.prompt_tokens(BIG, 5, 64, 50257)
    assert a != traffic.prompt_tokens(BIG, 6, 64, 50257)
    assert a != traffic.prompt_tokens(BIG + 1, 5, 64, 50257)
    assert all(0 <= t < 50257 for t in a)


def test_packed_batches():
    a = traffic.packed_batches(TRAIN, BIG, 50257, 128, 4, 3)
    assert a.shape == (3, 4, 128) and a.dtype == np.int32
    assert (a == traffic.packed_batches(TRAIN, BIG, 50257, 128, 4, 3)).all()
    assert (a != traffic.packed_batches(TRAIN, 7, 50257, 128, 4, 3)).any()
    assert a.min() >= 0 and a.max() < 50257
    big = traffic.packed_batches(TRAIN, 1, 50257, 1024, 4, 8)
    share = float(np.mean(big == TRAIN["eos_token_id"]))
    assert 1 / 2000 < share < 1 / 100  # an EOS every few hundred tokens
    _, counts = np.unique(big, return_counts=True)
    assert counts.max() > 50 * np.median(counts)  # Zipf: a few ids carry the mass


def test_loadgen_imports_no_jax():
    import subprocess
    import sys
    code = ("import sys; import chipbench.loadgen; "
            "sys.exit(any(m == 'jax' or m.startswith('jax.') or m == 'numpy' for m in sys.modules))")
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert subprocess.run([sys.executable, "-c", code], cwd=root).returncode == 0


# ---- reduce_records: plain arithmetic on the load generator's records


def _request(client, first, n, gap_s, t_send=None):
    """One finished request: ``n`` tokens, one an event, ``gap_s`` apart."""
    events = [(first + i * gap_s, 1) for i in range(n)]
    return {"client": client, "events": events, "t_first": first, "status": 200, "done": True,
            "max_tokens": n, "t_ready": (t_send or first) - 0.002,
            "t_send": t_send if t_send is not None else first - 0.001, "t_end": events[-1][0]}


def _reduce(records, stall_gap_ms=50, t0=0.0, t1=10.0):
    from chipbench.jobs.serve import reduce_records
    return reduce_records(records, t0, t1, t1 + 1.0, 16, stall_gap_ms)


def test_reduce_records_client_tpot_quantiles():
    # ten clients, one request each, at 10, 11, ..., 19 ms a token
    records = [_request(c, 0.5, 101, (10 + c) * 1e-3) for c in range(10)]
    res = _reduce(records)
    assert res["tpot_p50_ms"] == pytest.approx(14.5) and res["tpot_p90_ms"] == pytest.approx(18.1)
    assert res["info"]["tpot_p50_ms"] == res["tpot_p50_ms"] and res["info"]["tpot_samples"] == 10
    assert res["info"]["tpot_max_ms"] == pytest.approx(19.0)
    assert res["serve_tokens_per_s"] == pytest.approx(101.0) and res["failed"] == 0
    # a request with fewer than min_tokens tokens in the window has no gap of its own
    res = _reduce(records + [_request(10, 9.9, 40, 0.01)])
    assert res["info"]["tpot_samples"] == 10


def _interleaved(clients=4, period=0.016, until=9.99):
    """``clients`` streams, each a token every ``period``, phases spread."""
    n = int(until / period)
    return [_request(c, 0.001 + c * period / clients, n, period, t_send=-0.5)
            for c in range(clients)]


def test_reduce_records_no_stall():
    res = _reduce(_interleaved())
    assert res["delivery_stall_ms_per_s"] == 0.0 and res["delivery_stalls_per_min"] == 0.0
    assert res["info"]["delivery_stalls_longest"] == []
    # a cell that states no stall_gap_ms reports neither number
    res = _reduce(_interleaved(), stall_gap_ms=None)
    assert res["delivery_stall_ms_per_s"] is None and res["delivery_stalls_per_min"] is None


def test_reduce_records_one_stall_seen_by_all_clients():
    records = _interleaved()
    for r in records:  # at 5 s every client hears nothing for 80 ms more
        r["events"] = [(t + 0.080 if t >= 5.0 else t, n) for t, n in r["events"]]
    res = _reduce(records)
    assert res["delivery_stalls_per_min"] == pytest.approx(6.0)  # one in a 10 s window
    # the stall is the pause and the stream's own 4 ms between instants
    assert res["delivery_stall_ms_per_s"] == pytest.approx(8.4, abs=0.05)
    (at, ms), = res["info"]["delivery_stalls_longest"]
    assert 4.98 < at < 5.0 and ms == pytest.approx(84.0, abs=0.5)
    # the per-request gap hardly sees it: 80 ms over ~620 tokens
    assert res["tpot_p90_ms"] == pytest.approx(16.0 + 80 / 623, abs=0.01)


def test_reduce_records_one_clients_gap_is_no_stall():
    records = _interleaved()
    records[2]["events"] = [(t + 0.5 if t >= 5.0 else t, n) for t, n in records[2]["events"]]
    res = _reduce(records)
    assert res["delivery_stall_ms_per_s"] == 0.0 and res["delivery_stalls_per_min"] == 0.0
    assert res["tpot_p90_ms"] > res["tpot_p50_ms"]  # that client's own gap did grow


def test_reduce_records_stall_across_the_windows_edge_counts_its_part_inside():
    records = _interleaved(until=12.0)
    for r in records:  # silence from 9.9 s to 10.2 s, the window closes at 10
        r["events"] = [(t + 0.3 if t >= 9.9 else t, n) for t, n in r["events"]]
    res = _reduce(records)
    (at, ms), = res["info"]["delivery_stalls_longest"]
    assert at == pytest.approx(9.9, abs=0.01) and ms == pytest.approx(100.0, abs=5.0)
