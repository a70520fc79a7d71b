from chipbench.tests.test_traffic import *  # noqa: F401,F403
