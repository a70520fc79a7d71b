import traceback

from chipbench.tests import test_exaone_moe as _pr41
from chipbench.tests.test_exaone_moe import *  # noqa: F401,F403

KNOWN = 'assert serve_rate["workloads"][-1] == CELL'


def test_configuration_keeps_every_published_number(served):
    """PR 41's check, run as it stands and on the files as they stand. ONE of
    its assertions holds cell 8 to the LAST place among ``serve_tokens_per_s``'s
    cells, and PR 50 appended cell 9 behind it (membership is what it means: a
    ``benchmark`` issue's one-line edit, ``CHANGES.md``). Only that assertion's
    failure is accepted; what the check holds behind it (a cell reports exactly
    the metrics ``BENCHMARK.json`` lists for it, under the same ``why``, and is
    among the rate's cells) ``test_lfm2_moe``'s
    ``test_cell_reports_what_the_benchmark_lists`` holds for every cell."""
    try:
        _pr41.test_configuration_keeps_every_published_number(served)
    except AssertionError as failure:
        assert traceback.extract_tb(failure.__traceback__)[-1].line == KNOWN, failure
