from chipbench.tests.test_decode_trace import *  # noqa: F401,F403
