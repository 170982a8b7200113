"""CPU tests of the benchmark's own code. Run with
``python -m pytest perfbench/tests -q -p no:cacheprovider``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

# a result must not depend on what an earlier run left in the cache
jax.config.update("jax_enable_compilation_cache", False)
