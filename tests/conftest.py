import os

# Tests and benches must see the real (1-device) CPU backend — only the
# dry-run forces 512 host devices, and only in its own process.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Persistent compilation cache: the suite recompiles identical smoke-config
# HLO across many tests (three Trainers in the checkpoint test alone); the
# disk cache dedupes within a run and makes repeat runs much faster.  It
# honours JAX_COMPILATION_CACHE_DIR and otherwise sits in <checkout>/.jax_cache.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()
jax.config.update("jax_enable_x64", False)
