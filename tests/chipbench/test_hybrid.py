from chipbench.tests.test_hybrid import *  # noqa: F401,F403
