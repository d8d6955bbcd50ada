"""The benchmark: see benchmark/README.md."""

from __future__ import annotations

import importlib.util
import os


def find_data(root: str, paths: list, sub: str, filename: str) -> str | None:
    """`<root>/<path>/<sub>/<filename>` under the first of the manifest's
    `paths` that has it: how every per-cell file is found by name."""
    for p in paths:
        f = os.path.join(root, p, sub, filename)
        if os.path.exists(f):
            return f
    return None


def load_module(root: str, paths: list, sub: str, name: str):
    """The module `<path>/<sub>/<name>.py` under the first of `paths` that
    has it: a reader, a reference, an architecture's operation counts. So a
    later PR, or a test, brings its own file and edits none that is here."""
    f = find_data(root, paths, sub, f"{name}.py")
    if f is None:
        raise FileNotFoundError(f"no {sub}/{name}.py under {paths}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{sub}_" + name.replace(".", "_"), f)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
