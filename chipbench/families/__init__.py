"""What depends on a model family, found by the ``family`` that a
configuration file states: ``families/<family>.py``.

A family module defines ``Arch``, the family's sizes read from the
configuration file (``Arch.from_config``), with:

- ``program_config(config)``: the program's model configuration at those
  sizes, refusing a file the program's model does not match;
- ``leaf_specs()``: the parameter layout, ``{path: (shape, std)}``, and
  ``nest(flat)``, the tree the program takes;
- ``logits(w, tokens, ...)``: the plain reference's teacher-forced logits;
- ``decode_cost(...)`` and ``prefill_cost(...)``: operations and bytes of
  one serving step (``costs.Cost``).

Adding a family is adding its module; nothing else changes.
"""

from __future__ import annotations

import importlib


def arch(config: dict):
    """The sizes of ``config``, as its family's ``Arch``."""
    family = config["family"]
    try:
        module = importlib.import_module(f"{__name__}.{family}")
    except ModuleNotFoundError as e:
        raise ValueError(f"{config.get('name')}: no module for family "
                         f"{family!r} in chipbench/families") from e
    return module.Arch.from_config(config)
