"""Counts of a model's work, one module per model family; see
``perfbench/flops.py`` ``of`` and the README's "Adding things"."""
