from chipbench.tests.test_readers_60 import *  # noqa: F401,F403
