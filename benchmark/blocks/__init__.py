"""One decoder block per file, found by a configuration's published
``model_type``: ``benchmark/blocks/<model_type>.py``.

A block file is everything the benchmark knows about ONE architecture's
decoder block, and the only place that knows it: the leaves of a layer and
the model's tables with the rule each is drawn by (``weights.py`` is the
generator that draws them), the plain float32 reference of a layer and of the
logits with the two thresholds that decide ``correct`` (``reference.py`` is
the comparison), and the bytes one chip must read for a decode microstep
(``roofline.py`` holds what every block shares). ``README.md``, "A block",
lists what the file must give; ``tests/blocks/gpt2.py`` is a second one that
differs in every part, kept at tiny widths for the tests.
"""

from __future__ import annotations

import functools
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def find(model_type, folder: str = HERE) -> str:
    """The path of the block file of ``model_type``. A missing file is an
    error that names the path a PR has to add."""
    path = os.path.abspath(os.path.join(folder, f"{model_type}.py"))
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no block for model_type {model_type!r}: "
            f"{os.path.relpath(path, os.path.dirname(os.path.dirname(HERE)))} "
            "is missing (benchmark/README.md, 'A block')"
        )
    return path


@functools.lru_cache(maxsize=None)
def _load_file(path: str):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        "benchmark_block_" + "".join(c if c.isalnum() else "_" for c in name),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(model_type, folder: str = HERE):
    """The block module of ``model_type``; one module object per file, so it
    can key a cache or be a static argument."""
    return _load_file(find(model_type, folder))
