"""Plain references, one module per architecture, found by the ``arch`` key
of a configuration file: ``chipbench/reference/<arch>.py``. They import
nothing of the program.

An architecture's module holds everything of it that the benchmark's shared
modules need, so that a new architecture is a configuration, an
``arch/<arch>.py`` (the program's side) and this module, and nothing else:

- ``weight_spec(cfg)``: ``(name, shape, kind)`` of every weight, in the order
  of their seeds; ``kind`` is one of ``weights.py``'s initialisers (embed,
  matrix, scale, bias). ``STACKED``: the names whose leading axis is the
  layer, whose norms the training check takes per layer.
- ``token_ops(cfg)``, ``score_ops(cfg, keys)``, ``head_ops(cfg)`` and
  ``attention_kernel(cfg, S)``: the operation counts that ``flops.py`` sums
  (see there).
- The mathematics a cell's check runs: ``serve_gaps`` for serving cells;
  ``loss_and_grad`` and ``adamw`` for training cells.
"""
import importlib


def load(name):
    return importlib.import_module(f"chipbench.reference.{name}")
