from chipbench.tests.test_nemotron_h import *  # noqa: F401,F403
