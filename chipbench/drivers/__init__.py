"""Ways of driving the program, one module each, named by the ``driver``
key of a traffic mix: ``chipbench/drivers/<driver>.py`` exposes ``Driver``."""
import importlib


def load(name):
    return importlib.import_module(f"chipbench.drivers.{name}")
