from chipbench.tests.test_readers_59 import *  # noqa: F401,F403
