from chipbench.tests.test_xplane import *  # noqa: F401,F403
