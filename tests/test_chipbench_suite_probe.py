from chipbench.tests.test_probe import *  # noqa: F401,F403
