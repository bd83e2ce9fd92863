from chipbench.tests.test_prefill_computed_tokens import *  # noqa: F401,F403
