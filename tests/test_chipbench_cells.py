from chipbench.tests.test_cells import *  # noqa: F401,F403
