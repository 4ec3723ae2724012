"""Plain references, one module per architecture, found by the ``arch`` key
of a configuration file: ``chipbench/reference/<arch>.py``. They import
nothing of the program."""
import importlib


def load(name):
    return importlib.import_module(f"chipbench.reference.{name}")
