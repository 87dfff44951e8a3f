"""Model families, one module per Hugging Face ``model_type``:
``families/<model_type>.py``.  A configuration file registers only through
the module of its own ``model_type``; a type with no module stops the run.

A family module gives:

* ``program_fields(config) -> dict``: every ``ModelConfig`` field of the
  program that the file's published keys determine, by the program's name;
* ``refuse(config) -> list[str]``: the published keys whose stated values
  shape a layer in a way the program has no field for; registration stops
  and names each;
* the work counts of one forward pass, read by :mod:`work`:
  ``weight_bytes(config)``, ``kv_bytes_per_position(config)``,
  ``token_flops(config, context)``,
  ``decode_step(config, contexts, ctx=None)`` and
  ``prefill(config, n, start, ctx=None)``.  ``ctx`` is the per-layer
  reader's context (``harness.ReadContext``), for a family whose work
  depends on what the program did in the window, such as the experts a
  step touched; a family whose work follows from the shapes ignores it.

A new family is a new module here; nothing else changes.  The dense
decoder's counts are in :mod:`families._dense`, which a family may reuse.
"""

from __future__ import annotations

import importlib.util
import re
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
_LOADED: dict[Path, ModuleType] = {}


def load(model_type: str, directory: Path = HERE) -> ModuleType:
    """The family module of ``model_type`` in ``directory``; stops, naming
    the module, where there is none."""
    if not re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_]*", str(model_type)):
        raise ValueError(f"model_type {model_type!r} cannot name a family module")
    path = Path(directory) / f"{model_type}.py"
    if path not in _LOADED:
        if not path.is_file():
            raise ValueError(
                f"no family module families/{model_type}.py for model_type "
                f"{model_type!r}: add one to register this configuration"
            )
        spec = importlib.util.spec_from_file_location(
            f"families.{model_type}", path
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]
