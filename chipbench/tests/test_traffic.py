"""The generator: lengths and order repeat for a seed and differ across
seeds, and every seed does the same amount of work."""

import json
import os

import numpy as np

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
