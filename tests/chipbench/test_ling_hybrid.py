from chipbench.tests.test_ling_hybrid import *  # noqa: F401,F403
