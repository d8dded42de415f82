"""Traffic generators, found by the name a traffic file gives."""
import importlib


def load(name: str):
    return importlib.import_module(f"{__name__}.{name}")
