from chipbench.tests.test_sambay import *  # noqa: F401,F403
