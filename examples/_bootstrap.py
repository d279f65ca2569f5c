"""Put the repo root on sys.path so the examples run from a checkout
(`python examples/foo.py`) without installation, and turn on the
persistent compile cache (``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``). Import this before byteps_tpu in every
example."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from byteps_tpu.common.config import enable_compile_cache  # noqa: E402

enable_compile_cache()
