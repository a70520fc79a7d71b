from chipbench.tests.test_lfm2_moe import *  # noqa: F401,F403
