"""Pytest plumbing: force an 8-device virtual CPU mesh so DP/TP/PP/EP/SP
logic runs under pytest without a pod (SURVEY §4 'implications').

The tests run on the CPU backend with Pallas kernels in interpret mode:
they check results and count programs, tokens and bytes, and give no time
or rate. ``jax.config.update`` works as long as no backend has been
initialized yet; no compile cache is configured here.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    yield
    from deepspeed_tpu.comm import comm
    comm._state["mesh"] = None
    comm._state["comms_logger"] = None


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running multi-process tests")


# ---------------------------------------------------------------------------
# fast / slow lanes (reference CI splits sequential/parallel lanes, SURVEY §4;
# VERDICT r3 item 10: a red test must not hide behind a 10-minute wall).
#
#   core lane:  pytest tests/ -m "not slow"     (~3 min)
#   slow lane:  pytest tests/ -m slow
#
# tests/slow_tests.txt is the measured duration table (nodeids >= 15s on the
# single-core dev box); regenerate with
#   pytest tests/ -q --durations=0 | awk '$1+0>=15 && $2=="call" {print $3}'
# New tests default to the core lane until measured.
# ---------------------------------------------------------------------------
_SLOW_FILE = os.path.join(os.path.dirname(__file__), "slow_tests.txt")


def _slow_set():
    try:
        with open(_SLOW_FILE) as f:
            return {ln.strip() for ln in f if ln.strip()}
    except OSError:
        return set()


def pytest_collection_modifyitems(config, items):
    slow = _slow_set()
    if not slow:
        return
    marker = pytest.mark.slow
    for item in items:
        base = item.nodeid.split("[")[0]
        if item.nodeid in slow or base in slow:
            item.add_marker(marker)
