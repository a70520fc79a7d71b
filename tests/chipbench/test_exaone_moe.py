from chipbench.tests.test_exaone_moe import *  # noqa: F401,F403
