"""``attn_walk_live_pct`` (PR 35), a data file over the built-in
``counter_ratio``: the share of the keys the paged decode kernel's walk
fetches that lie inside the rows' attended windows, from the scheduler's
``serving/attn_keys_live`` / ``serving/attn_keys_walked``. Read from a real
sink snapshot of a pump whose attention runs the kernel; a program without
the counters (the parent) gives nothing and raises nothing; listed for the
cells whose models run the kernel, as its file says."""

import json
import os

import numpy as np
import pytest

import deepspeed_tpu
from chipbench import cells, reducers
from deepspeed_tpu.comm import comm
from deepspeed_tpu.telemetry import set_sink

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "attn_walk_live_pct"
CELLS = ["gpt2-large.serve.chat-closed", "olmo-hybrid-7b.serve.decode-closed",
         "phi-4-mini-flash.serve.reason-closed"]


def _spec():
    with open(os.path.join(ROOT, "chipbench", "metrics", NAME + ".json")) as f:
        return json.load(f)


def _reduce(obs):
    spec = _spec()
    return reducers.BUILTIN[spec["reducer"]](spec["args"], obs)


@pytest.mark.parametrize("kernel", [True, False], ids=["paged_kernel", "xla_attention"])
def test_reads_the_schedulers_counters(tmp_path, kernel):
    comm._state["mesh"] = None
    set_sink(None)
    eng = deepspeed_tpu.init_inference("tiny", config={
        "dtype": "float32", "max_out_tokens": 128, "kernel_inject": kernel, "decode_block_kv": 32,
        "continuous_batching": {"enabled": True, "num_slots": 3, "steps_per_sync": 2,
                                "prefill_chunk": 16},
        "telemetry": {"enabled": True, "output_path": str(tmp_path)}})
    try:
        sched = eng.scheduler()
        rng = np.random.default_rng(5)
        for n in (40, 17):
            sched.submit(rng.integers(0, 256, n).astype(np.int32), max_new_tokens=12)
        sched.drain()
        obs = {"telemetry": eng.telemetry.snapshot()}
    finally:
        eng.telemetry.close()
        set_sink(None)
    counters = obs["telemetry"]["counters"]
    if not kernel:  # XLA's attention walks no blocks: nothing counted, nothing read
        assert "serving/attn_keys_walked" not in counters and _reduce(obs) is None
        return
    live, walked = (counters[f"serving/attn_keys_{k}"]["total"] for k in ("live", "walked"))
    assert 0 < live <= walked and walked % 32 == 0
    assert _reduce(obs) == pytest.approx(100.0 * live / walked)


@pytest.mark.parametrize("obs", [{}, {"telemetry": {"counters": {}}},
                                 {"telemetry": {"counters": {"serving/syncs_ahead": {"count": 5, "total": 5}}}}],
                         ids=["no_sink", "empty", "parent"])
def test_nothing_to_read_on_the_parent(obs):
    assert _reduce(obs) is None


def test_hand_count():
    counters = {"serving/attn_keys_live": {"count": 3, "total": 230 * 19},
                "serving/attn_keys_walked": {"count": 3, "total": 360 * 19}}
    assert _reduce({"telemetry": {"counters": counters}}) == pytest.approx(100.0 * 230 / 360)


def test_listed_where_it_can_be_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(NAME) - 1] == "delivery_stalls_per_min"  # appended behind PR 38's
    spec = _spec()
    # cells added since name the metric in their own file and join the list here
    assert spec["workloads"] == CELLS == entry["workloads"][:len(CELLS)]
    assert {k: entry[k] for k in ("layer", "moves", "source", "unit", "better")} == {
        k: spec[k] for k in ("layer", "moves", "source", "unit", "better")} == {
            "layer": "kernels", "moves": "serve_tokens_per_s", "source": "program_counter",
            "unit": "%", "better": "higher"}
    for cell in bench["workloads"]:
        _, workload, root = cells.load_workload(cell["name"])
        assert (NAME in cells.per_layer_metrics(cell["name"], workload, root)) == (
            cell["name"] in entry["workloads"]), cell["name"]
    assert cells.custom_reducer(dict(spec, name=NAME, dir=os.path.join(
        ROOT, "chipbench", "metrics"))) is None  # data alone: no reader
