"""Plain references, one module per model family; a configuration file
names its module under "reference"."""
import importlib


def load_reference(config: dict):
    return importlib.import_module(f"{__name__}.{config['reference']}")
