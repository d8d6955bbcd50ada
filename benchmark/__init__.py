"""The benchmark: see benchmark/README.md."""

from __future__ import annotations

import os


def find_data(root: str, paths: list, sub: str, filename: str) -> str | None:
    """`<root>/<path>/<sub>/<filename>` under the first of the manifest's
    `paths` that has it: how every per-cell file is found by name."""
    for p in paths:
        f = os.path.join(root, p, sub, filename)
        if os.path.exists(f):
            return f
    return None
