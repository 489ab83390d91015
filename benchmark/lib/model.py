"""The benchmark's files, found by name, and the two modules that a
configuration file names.

The file holds the model's published keys (as in its ``config.json``)
and, under ``assumed``, what a deployment sets itself.  ``reference``
names its plain reference (``reference/<name>.py``, which imports
nothing of the program) and ``program`` its program module
(``programs/<name>.py``, the only code here that imports the program's
model and engine classes).  The harness knows no model family: what is
a family's — the configuration object, the layout of the weights, the
entry points, the required work — it reaches through ``program_module``.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def dtype_of(config: Dict[str, Any]) -> str:
    """The dtype the configuration states, as JAX names it."""
    name = config["torch_dtype"]
    if name not in ("bfloat16", "float32", "float16"):
        raise ValueError(f"unknown torch_dtype {name!r}")
    return name


def load_json(kind: str, name: str, root: str = HERE) -> Dict[str, Any]:
    """``<root>/<kind>/<name>.json`` — how every cell, configuration,
    traffic mix and metric is found: by its name."""
    path = os.path.join(root, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def reference_module(config: Dict[str, Any]):
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


def program_module(config: Dict[str, Any]):
    """``programs/<program>.py`` — the one seam to the system under
    test: the family's configuration object, weight layout, entry
    points and required work.  The key is required: a configuration
    that named no program would be run as some other family's."""
    return importlib.import_module(
        f"benchmark.programs.{config['program']}")
