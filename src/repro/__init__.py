"""OpenACM-on-TPU reproduction.

Heavy subsystems load lazily: `repro.autoallocate` is the one-command
per-module accuracy allocator (DESIGN.md §16) without forcing JAX/model
imports on package import.
"""

import os as _os

# Root of the checkout (src/repro/ -> ../..).  Every cache the package
# keeps on disk lives under it (`.cache/`, `.jax_cache/`, git-ignored).
CHECKOUT = _os.path.dirname(_os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))

_LAZY = {
    "autoallocate": ("repro.core.allocate", "autoallocate"),
    "Allocation": ("repro.core.allocate", "Allocation"),
    "exhaustive_oracle": ("repro.core.allocate", "exhaustive_oracle"),
}

__all__ = sorted(_LAZY)


def __getattr__(name):
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    import importlib

    return getattr(importlib.import_module(mod_name), attr)
