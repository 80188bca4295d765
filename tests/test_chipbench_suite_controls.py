from chipbench.tests.test_controls import *  # noqa: F401,F403
