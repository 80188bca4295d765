from chipbench.tests.test_readers_57 import *  # noqa: F401,F403
