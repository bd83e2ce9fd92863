from chipbench.tests.test_sarvam_cell import *  # noqa: F401,F403
