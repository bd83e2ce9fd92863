from chipbench.tests.test_exaone_moe_cell import *  # noqa: F401,F403
