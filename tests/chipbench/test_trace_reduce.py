from chipbench.tests.test_trace_reduce import *  # noqa: F401,F403
