from chipbench.tests.test_loadgen import *  # noqa: F401,F403
