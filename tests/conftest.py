"""Settings shared by every test file.

JAX's persistent compilation cache stays off in the tests and in every
process they start (``JAX_ENABLE_COMPILATION_CACHE`` is inherited): the
entry points switch it on (``repro.launch.chip.enable_compile_cache``),
and a compile for a described TPU topology written there could not be
read back without the chip.
"""
import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402  (after the variable, so import-time config sees it)

jax.config.update("jax_enable_compilation_cache", False)
