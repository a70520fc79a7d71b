from chipbench.tests.test_mla_moe import *  # noqa: F401,F403
