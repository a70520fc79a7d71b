from chipbench.tests.test_harness import *  # noqa: F401,F403
