"""A file a configuration names: ``"reference": "<name>"`` is
``references/<name>.py``, ``"cost_model": "<name>"`` is
``cost_models/<name>.py``. A name with no file is an error (``LookupError``),
never a default: the default is what runs where the key is absent."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def path_of(directory: str, name: str) -> str:
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.isfile(path):
        raise LookupError(f"the configuration names {name!r} and "
                          f"benchmarks/{directory}/{name}.py is not there")
    return path


def load(directory: str, name: str):
    spec = importlib.util.spec_from_file_location(
        directory + "_" + name.replace(".", "_").replace("-", "_"),
        path_of(directory, name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
