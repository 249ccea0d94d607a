import os
import sys

# tests run on the default single CPU device; distributed tests that need
# multiple devices spawn subprocesses (see test_distributed.py) so the
# device count is NOT forced globally here (per the dry-run contract).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
