from chipbench.tests.test_falcon_h1 import *  # noqa: F401,F403
