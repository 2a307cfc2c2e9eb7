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

A block whose layers are not all alike also gives ``layer_kinds(model)``, one
kind name per layer; ``kinds`` below is how the shared code asks, and a block
without it is read exactly as before there was such a thing. A block whose
layers run several times for one token gives ``passes(model)`` (and, as a
rule, ``close_pass``); ``passes`` below is how the shared code asks, the same
way.
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


def kinds(block, model: dict):
    """One kind name per layer, in layer order — or None for a block whose
    layers are all alike (it has no ``layer_kinds``)."""
    if not hasattr(block, "layer_kinds"):
        return None
    got = tuple(block.layer_kinds(model))
    layers = block.dims(model)["layers"]
    if len(got) != layers:
        raise ValueError(
            f"layer_kinds names {len(got)} layers, the model has {layers}")
    return got


def looped(block) -> bool:
    """Whether the block says how often its layers run. Such a block's
    ``logits`` is handed the closed state of every pass, ``[T, rows, H]``,
    also where T is 1."""
    return hasattr(block, "passes")


def passes(block, model: dict) -> int:
    """How many times the stack of layers runs for one token: the block's
    ``passes(model)``, or 1 for a block whose layers run once (it has no
    ``passes``)."""
    if not looped(block):
        return 1
    got = int(block.passes(model))
    if got < 1:
        name = os.path.basename(getattr(block, "__file__", block.__name__))
        raise ValueError(
            f"block {name}: passes(model) says the layers run {got} times, "
            "and they run once at least")
    return got


def place(kinds_, layer: int):
    """Where layer ``layer`` lies in the parameter tree: ``(kind, index in
    that kind's stack)``; ``(None, layer)`` where there are no kinds. A
    kind's stack holds its layers in layer order."""
    if kinds_ is None:
        return None, layer
    kind = kinds_[layer]
    return kind, kinds_[:layer].count(kind)


def static_of(block, model: dict, kinds_, layer: int) -> dict:
    """The static keywords of ``layer_forward`` for layer ``layer``: the
    block's ``layer_static`` — which a block with kinds may give per kind,
    ``{kind: {...}}`` — and, with kinds, ``kind=`` itself."""
    static = block.layer_static(model)
    if kinds_ is None:
        return dict(static)
    if static and all(isinstance(v, dict) for v in static.values()):
        static = static[kinds_[layer]]
    return dict(static, kind=kinds_[layer])
