"""Plain references, one module per model family; a configuration file names
its own under ``"reference"``."""

import importlib


def of(config: dict):
    """The reference module of a configuration (``perfbench/reference/<name>.py``)."""
    return importlib.import_module(f"perfbench.reference.{config['reference']}")
