"""What the entry points need to know about the chip before using it.

* :func:`enable_compile_cache` — JAX's persistent compilation cache for
  the entry points (``launch/train.py``, ``launch/serve.py``,
  ``launch/tune.py``, ``chip_smoke.py``).  Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is set
  here; otherwise the cache lives at one fixed, git-ignored path inside
  the checkout, so that every process of every run shares it.  Entries
  are keyed by the backend and device, so an entry written on the CPU
  never answers a compile for the chip.
* :func:`tpu_host` — whether this process may reach a TPU chip, decided
  without starting a JAX backend (a process that starts one holds the
  chip, and a second process then cannot have it).
"""
from __future__ import annotations

import glob
import os
import pathlib

COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / \
    ".jax_compile_cache"

_GOOGLE_PCI_VENDOR = "0x1ae0"
#: PCI device ids of TPU chips (v4, v5p, v5e, v6e)
_TPU_PCI_DEVICES = {"0x005e", "0x0062", "0x0063", "0x006f"}


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def tpu_chips() -> int:
    """TPU chips attached to this host, read from the PCI bus."""
    n = 0
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        dev = pathlib.Path(vendor).with_name("device")
        try:
            if (pathlib.Path(vendor).read_text().strip() == _GOOGLE_PCI_VENDOR
                    and dev.read_text().strip() in _TPU_PCI_DEVICES):
                n += 1
        except OSError:
            continue
    return n


def tpu_host() -> bool:
    """True when a TPU chip is attached and ``JAX_PLATFORMS`` does not
    shut JAX out of it."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    return tpu_chips() > 0
