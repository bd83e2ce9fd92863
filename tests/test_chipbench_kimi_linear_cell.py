from chipbench.tests.test_kimi_linear_cell import *  # noqa: F401,F403
