from chipbench.tests.test_mimo_v2_cell import *  # noqa: F401,F403
