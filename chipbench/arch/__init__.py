"""The program's side of each architecture, found by the ``arch`` key of a
configuration file: ``chipbench/arch/<arch>.py``."""
import importlib


def load(name):
    return importlib.import_module(f"chipbench.arch.{name}")
