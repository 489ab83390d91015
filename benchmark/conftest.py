"""What the benchmark's CPU tests need to know of the cells that came
after the first three, as data: ``tests/tiny.py`` makes a tiny twin of
every file under ``configs/``, ``workloads/`` and ``metrics/`` and names
the twins of the cells it was written with.  A later configuration adds
ONE file, ``tests/data/tiny/<configuration>.json``, and no code::

    {"cells": {"<cell>": "<its tiny twin's name>", ...},
     "config": {"<key>": <tiny value>, ...}}

``cells`` joins ``tiny.CELLS``; ``config`` is laid over the tiny twin of
``configs/<configuration>.json`` (the keys ``tiny.TINY_WIDTHS`` does not
name: a family whose widths are other keys than the dense decoders').
Loaded by pytest before ``tests/conftest.py``; nothing of a benchmark
run reads it.  (The hook belongs in ``tests/tiny.py``: a ``benchmark``
issue's move, since this PR may edit no file the benchmark has.)"""

import glob
import json
import os

from benchmark.tests import tiny

TINY_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tests", "data", "tiny")


def tiny_files():
    """``{configuration: its file's object}`` for every file there."""
    out = {}
    for path in sorted(glob.glob(os.path.join(TINY_DIR, "*.json"))):
        with open(path) as f:
            out[os.path.basename(path)[:-5]] = json.load(f)
    return out


for _entry in tiny_files().values():
    for _cell, _twin in _entry.get("cells", {}).items():
        tiny.CELLS.setdefault(_cell, _twin)

_make_root = tiny.make_root


def make_root(dst, limits=None):
    dst = _make_root(dst, limits)
    for name, entry in tiny_files().items():
        path = os.path.join(dst, "configs", name + ".json")
        with open(path) as f:
            cfg = json.load(f)
        with open(path, "w") as f:
            json.dump(dict(cfg, **entry.get("config", {})), f)
    return dst


tiny.make_root = make_root
