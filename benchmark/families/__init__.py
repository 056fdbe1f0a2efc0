"""Model families: one file a family, ``<model>.py``, found from a
configuration's ``model`` by listing this directory (``spec.load_family``).
A later PR adds a family by adding its file; the runners name none.
``benchmark/README.md``, "Adding a family", is the one place that states the
names a family's file gives and the surface of the engine and the cache that
the serve runner uses; ``llama.py`` is the example.
"""

from __future__ import annotations

from typing import Any, NamedTuple


class ServeSystem(NamedTuple):
    """What ``build_serve`` returns (any object with these attributes will do)."""

    params: Any
    cache: Any      # of whatever class the program's scheduler takes
    engine: Any
    vocab: int      # the traffic and the check draw their token ids below it


class TrainSystem(NamedTuple):
    """What ``build_train`` returns."""

    module: Any     # what ``parallelize_module`` takes
    plan: Any       # its sharding plan for the runner's mesh
    vocab: int
