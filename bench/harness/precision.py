"""Switch the program's selection-dot precision from the benchmark's side:
the lower-precision control of the correctness check.

The program states one precision for every selection dot
(``repro.kernels.common.SELECT_PRECISION``, HIGHEST) and each module that
computes one reads the name it imported at trace time; this rebinds it in
every loaded ``repro`` module.  Only passes traced afterwards see it.
"""
from __future__ import annotations

import sys

NAME = "SELECT_PRECISION"


def set_select_precision(name: str) -> list[str]:
    """Rebind the precision (``highest``, ``high`` or ``default``); returns
    the modules changed."""
    import jax

    value = getattr(jax.lax.Precision, name.upper())
    changed = []
    for mod_name, mod in list(sys.modules.items()):
        if mod_name.startswith("repro.") and hasattr(mod, NAME):
            setattr(mod, NAME, value)
            changed.append(mod_name)
    return changed
