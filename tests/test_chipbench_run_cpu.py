from chipbench.tests.test_run_cpu import *  # noqa: F401,F403
