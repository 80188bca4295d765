from chipbench.tests.test_measure import *  # noqa: F401,F403
