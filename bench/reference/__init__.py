"""Plain float32 references, one module per architecture family, named by
the ``reference`` key of a configuration file."""
import importlib


def load_reference(name: str):
    return importlib.import_module(f"bench.reference.{name}")
