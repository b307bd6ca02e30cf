"""The benchmark's own tests run on the CPU, at tiny sizes, with JAX's
persistent compilation cache off: ``python -m pytest bench/``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.join(os.path.dirname(HERE), "src"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
