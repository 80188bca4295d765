from chipbench.tests.test_manifest import *  # noqa: F401,F403
