from chipbench.tests.test_serve_chat_fits import *  # noqa: F401,F403
