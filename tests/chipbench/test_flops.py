from chipbench.tests.test_flops import *  # noqa: F401,F403
