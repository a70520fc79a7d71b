from chipbench.tests.test_reference import *  # noqa: F401,F403
