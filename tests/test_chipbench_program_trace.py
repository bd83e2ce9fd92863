from chipbench.tests.test_program_trace import *  # noqa: F401,F403
