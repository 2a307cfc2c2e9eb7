"""Shard store: offline checkpoint splitting + role-conditional stage loading.

TPU-native counterpart of the reference's ``ModelSharder.save_shards``
(``/root/reference/utils/model_sharder.py:48-134``) and the loading side spread
across ``NodeWorker.load_shards`` / ``LlamaShardPart``
(``utils/node_worker.py:127-185``, ``utils/shard_loader.py:13-55``).

Layout mirrors the reference's split logically — one file per unit —

    <out_dir>/                       # dtype-tagged, e.g. llama2-7b_bfloat16
      config.json                    # ModelConfig (≙ copied HF config.json)
      tokenizer.*                    # copied tokenizer files (non-weight)
      embedding.npz                  # ≙ embedding.pth   (embed [+pos_embed])
      block_{i}.npz                  # ≙ block_{i}.pth   (one decoder layer)
      final_norm.npz                 # ≙ final_norm.pth / ln_f.pth
      lm_head.npz                    # ≙ lm_head.pth (absent when tied: the
                                     #   last stage reuses embedding.npz)
      <unit>.part{j}.npz             # the rest of a unit bigger than one file
                                     #   may be (``MAX_FILE_BYTES``)

— but stores numpy ``.npz`` instead of torch pickles, and the loader stacks a
stage's ``block_{start..end-1}`` into scan-ready ``[L, ...]`` arrays.

Role-conditional loading reproduces the reference's conditionals exactly:
embedding iff the stage can receive user requests (``node_worker.py:105-107``),
final-norm + lm_head iff ``end == num_hidden_layers`` (``:155-164``). RoPE
needs no table loading — recomputed from positions (see ``ops/rope.py``).

Conversion can stream tensor-by-tensor from safetensors, so no machine ever
holds the whole model — the reference requires one big-memory machine for this
step (``/root/reference/README.md:29``).
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from ..models.family import family
from .convert import (
    TensorGetter,
    _getter,
    _refuse_unmapped,
    gpt2_head_arrays,
)

# Tokenizer/config files copied verbatim, skipping weights — the same skip
# rule as /root/reference/utils/model_sharder.py:50-61.
_WEIGHT_SUFFIXES = (".bin", ".safetensors", ".pth", ".pt", ".gguf")


# numpy's npz format cannot round-trip ml_dtypes extension types (bf16 etc.
# are written as raw void and cannot be cast back on load), so such arrays
# are stored as same-width integer views plus a `<name>__dtype` tag.
# Int8-quantized weights (ops/quant.QTensor) are stored as a `<name>__q`
# int8 array + `<name>__scale` pair and reassembled on load (≙ the
# reference's load_in_8bit stores, ``model_sharder.py:28-45`` — quantized on
# disk AND in device memory). Int4 weights (ops/quant.Int4QTensor, ≙
# load_in_4bit) store TWO values per byte as `<name>__q4` (packed along the
# last axis, odd sizes padded) + a `<name>__q4dim` last-axis size; they load
# back as int8-resident Int4QTensors (see that class for why HBM residence
# stays int8 on this stack).
_DTYPE_TAG = "__dtype"
_Q_SUFFIX = "__q"
_Q4_SUFFIX = "__q4"
_Q4_DIM_TAG = "__q4dim"
_SCALE_SUFFIX = "__scale"
_INT_VIEW = {1: np.uint8, 2: np.uint16, 4: np.uint32}

# No file of a store carries more than this many array bytes, so a store can
# be written where a per-file size limit holds (an RLIMIT_FSIZE, a 2-4 GiB
# filesystem cap): a unit that is bigger continues in `<unit>.part<j>.npz`,
# the first file's `__files` says how many there are, and an array bigger
# than a file is cut along its leading axis into `<name>__part<j>` pieces
# (a 7B vocab table is 1.1 GB in bf16). Loading joins them on the host.
MAX_FILE_BYTES = 128 << 20
_FILES_TAG = "__files"
_PART_TAG = "__part"


def _pack_int4(a: np.ndarray) -> np.ndarray:
    """int8 values in [-8, 7] → packed bytes, pairs along the last axis
    (lo nibble = even index, hi nibble = odd index)."""
    a = np.asarray(a, np.int8)
    if a.shape[-1] % 2:
        a = np.concatenate([a, np.zeros((*a.shape[:-1], 1), np.int8)], axis=-1)
    lo = a[..., 0::2] & 0xF
    hi = a[..., 1::2] & 0xF
    return (lo | (hi << 4)).astype(np.int8)


def _unpack_int4(p: np.ndarray, last_dim: int) -> np.ndarray:
    """Packed bytes → int8 values (arithmetic shifts restore the sign)."""
    p = np.asarray(p, np.int8)
    lo = (p << 4) >> 4
    hi = p >> 4
    out = np.stack([lo, hi], axis=-1).reshape(*p.shape[:-1], -1)
    return out[..., :last_dim]


def _encode_array(out: dict, k: str, v) -> None:
    a = np.asarray(v)
    if a.dtype.kind == "V":  # ml_dtypes extension types report kind 'V'
        out[k] = a.view(_INT_VIEW[a.dtype.itemsize])
        out[k + _DTYPE_TAG] = np.asarray(a.dtype.name)
    else:
        out[k] = a


def _save_npz(path: str, arrays: dict[str, Any]) -> None:
    from ..ops.quant import Int4QTensor, QTensor

    out: dict[str, np.ndarray] = {}
    for k, v in arrays.items():
        if isinstance(v, Int4QTensor):
            q = np.asarray(v.q)
            out[k + _Q4_SUFFIX] = _pack_int4(q)
            out[k + _Q4_DIM_TAG] = np.asarray(q.shape[-1])
            _encode_array(out, k + _SCALE_SUFFIX, v.scale)
        elif isinstance(v, QTensor):
            _encode_array(out, k + _Q_SUFFIX, v.q)
            _encode_array(out, k + _SCALE_SUFFIX, v.scale)
        else:
            _encode_array(out, k, v)
    files = _split_files(out, MAX_FILE_BYTES)
    if len(files) > 1:
        files[0][_FILES_TAG] = np.asarray(len(files))
    for j, part in enumerate(files):
        np.savez(_part_path(path, j), **part)


def _part_path(path: str, j: int) -> str:
    """File ``j`` of the unit at ``path`` (``<unit>.npz``)."""
    return path if j == 0 else f"{path[: -len('.npz')]}.part{j}.npz"


def _split_files(
    out: dict[str, np.ndarray], limit: int
) -> list[dict[str, np.ndarray]]:
    """Spread one unit's encoded arrays over files of at most ``limit``
    array bytes, in order. An array bigger than ``limit`` is cut along its
    leading axis (views, no copy); a row is never cut, so a row bigger than
    ``limit`` gets a file to itself."""
    pieces: list[tuple[str, np.ndarray]] = []
    for k, a in out.items():
        if a.nbytes <= limit or a.ndim == 0:
            pieces.append((k, a))
            continue
        rows = max(limit // (a.nbytes // len(a)), 1)
        pieces += [
            (f"{k}{_PART_TAG}{j}", a[at: at + rows])
            for j, at in enumerate(range(0, len(a), rows))
        ]
    files: list[dict[str, np.ndarray]] = [{}]
    room = limit
    for k, a in pieces:
        if a.nbytes > room and files[-1]:
            files.append({})
            room = limit
        files[-1][k] = a
        room -= a.nbytes
    return files


def _read_unit(path: str) -> dict[str, np.ndarray]:
    """The encoded arrays of one unit: its continuation files merged and
    its cut arrays joined (the inverse of ``_split_files``)."""
    with np.load(path) as z:
        raw = {k: z[k] for k in z.files}
    for j in range(1, int(raw.pop(_FILES_TAG, 1))):
        with np.load(_part_path(path, j)) as z:
            raw.update({k: z[k] for k in z.files})
    cut: dict[str, dict[int, np.ndarray]] = {}
    for k in list(raw):
        base, tag, j = k.rpartition(_PART_TAG)
        if tag:
            cut.setdefault(base, {})[int(j)] = raw.pop(k)
    for base, pieces in cut.items():
        raw[base] = np.concatenate([pieces[j] for j in range(len(pieces))])
    return raw


def _load_npz(path: str, dtype) -> dict[str, Any]:
    """One store unit → HOST arrays (numpy / ml_dtypes), QTensor leaves
    included. Nothing here touches a device: the engine keeps the loaded
    model as its host-resident repartition source and places each stage's
    slice on that stage's chip — a model bigger than one chip must never
    detour through the default device on its way in."""
    import ml_dtypes

    from ..ops.quant import Int4QTensor, QTensor

    dtype = np.dtype(dtype)

    def decode(z, k) -> np.ndarray:
        a = z[k]
        tag = k + _DTYPE_TAG
        if tag in z:
            a = a.view(np.dtype(getattr(ml_dtypes, str(z[tag]))))
        return a

    z = _read_unit(path)
    res: dict[str, Any] = {}
    for k in z:
        if (
            k.endswith(_DTYPE_TAG)
            or k.endswith(_SCALE_SUFFIX)
            or k.endswith(_Q4_DIM_TAG)
        ):
            continue
        if k.endswith(_Q4_SUFFIX):
            base = k[: -len(_Q4_SUFFIX)]
            res[base] = Int4QTensor(
                # int8-resident (see Int4QTensor)
                q=_unpack_int4(z[k], int(z[base + _Q4_DIM_TAG])),
                scale=decode(z, base + _SCALE_SUFFIX).astype(dtype, copy=False),
            )
        elif k.endswith(_Q_SUFFIX):
            base = k[: -len(_Q_SUFFIX)]
            res[base] = QTensor(
                q=decode(z, k),  # stays int8
                scale=decode(z, base + _SCALE_SUFFIX).astype(dtype, copy=False),
            )
        else:
            res[k] = decode(z, k).astype(dtype, copy=False)
    return res


def save_shards(
    cfg: ModelConfig,
    src: Any,  # full params pytree (from models/*.init_params or convert)
    out_dir: str,
    tokenizer_dir: Optional[str] = None,
) -> None:
    """Split a full params pytree into the per-unit store."""
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        f.write(cfg.to_json())
    if tokenizer_dir:
        copy_tokenizer_files(tokenizer_dir, out_dir)

    emb = {"embed": src["embed"]}
    if "pos_embed" in src:
        emb["pos_embed"] = src["pos_embed"]
    _save_npz(os.path.join(out_dir, "embedding.npz"), emb)

    layers = src["layers"]
    kinds = cfg.layer_kinds
    for i in range(cfg.num_hidden_layers):
        # tree.map slices through QTensor leaves (q AND scale) correctly. A
        # model whose layers are of several kinds keeps one stack per kind
        # (in layer order); a block file is one layer whatever its kind
        stack, j = (layers, i) if not kinds else (
            layers[kinds[i]], kinds[:i].count(kinds[i])
        )
        _save_npz(
            os.path.join(out_dir, f"block_{i}.npz"),
            jax.tree.map(lambda a, j=j: a[j], stack),
        )

    fn = {"final_norm": src["final_norm"]}
    if "final_norm_bias" in src:
        fn["final_norm_bias"] = src["final_norm_bias"]
    _save_npz(os.path.join(out_dir, "final_norm.npz"), fn)
    if "lm_head" in src:  # tied models reuse embedding.npz (no duplicate)
        _save_npz(os.path.join(out_dir, "lm_head.npz"), {"lm_head": src["lm_head"]})


def save_shards_streaming(
    cfg: ModelConfig,
    src: TensorGetter | dict,
    out_dir: str,
    dtype=jnp.bfloat16,
    tokenizer_dir: Optional[str] = None,
    quantize: bool = False,
    quantize_head: bool = False,
    quant_bits: int = 8,
) -> None:
    """Split directly from an HF name→tensor source, one unit at a time.
    ``quantize`` stores layer matmul weights quantized (per-output-channel
    scales in ``dtype``) — ≙ the reference's ``load_in_8bit``/``load_in_4bit``
    conversion modes (``model_sharder.py:28-45``), with ``quant_bits``
    selecting 8 (int8) or 4 (nibble-packed on disk); norms stay ``dtype``.
    The vocab tables stay ``dtype`` too unless ``quantize_head`` (embed
    per-ROW scales, untied lm_head per-column — see
    ``ops/quant.quantize_params``).
    """
    from ..ops.quant import quantize_layer_params, quantize_tensor

    _refuse_unmapped(cfg)  # before anything is written

    def maybe_q_embed(t):  # [V, H]: scale per vocab row
        if not quantize_head:
            return t
        return quantize_tensor(t, contract_axis=-1, bits=quant_bits)

    get = _getter(src)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        f.write(cfg.to_json())
    if tokenizer_dir:
        copy_tokenizer_files(tokenizer_dir, out_dir)

    fam = family(cfg)
    for i in range(cfg.num_hidden_layers):
        block = fam.layer_arrays(cfg, get, i, dtype)
        if quantize:
            block = quantize_layer_params(block, bits=quant_bits)
        _save_npz(os.path.join(out_dir, f"block_{i}.npz"), block)

    if not fam.learned_positions:
        # a model with a share of the experts may hold a SLICE of the
        # vocabulary: rows 0..V-1
        V = cfg.vocab_size
        embed_name, norm_name = fam.head_names
        embed = jnp.asarray(get(embed_name)[:V], dtype)
        _save_npz(
            os.path.join(out_dir, "embedding.npz"),
            {"embed": maybe_q_embed(embed)},
        )
        _save_npz(
            os.path.join(out_dir, "final_norm.npz"),
            {"final_norm": jnp.asarray(get(norm_name), dtype)},
        )
        if not cfg.tie_word_embeddings:
            head = jnp.asarray(get("lm_head.weight")[:V].T, dtype)
            if quantize_head:
                head = quantize_tensor(head, contract_axis=-2, bits=quant_bits)
            _save_npz(os.path.join(out_dir, "lm_head.npz"), {"lm_head": head})
    else:  # gpt2's checkpoint; lm_head tied to wte — nothing extra to save
        head = gpt2_head_arrays(get, dtype)
        _save_npz(
            os.path.join(out_dir, "embedding.npz"),
            {
                "embed": maybe_q_embed(head["embed"]),
                "pos_embed": head["pos_embed"],
            },
        )
        _save_npz(
            os.path.join(out_dir, "final_norm.npz"),
            {k: head[k] for k in ("final_norm", "final_norm_bias")},
        )


def copy_tokenizer_files(src_dir: str, out_dir: str) -> None:
    """Copy config/tokenizer files, skipping weights (≙ the skip rule at
    ``/root/reference/utils/model_sharder.py:50-61``)."""
    for name in os.listdir(src_dir):
        p = os.path.join(src_dir, name)
        if not os.path.isfile(p):
            continue
        if (
            name.endswith(_WEIGHT_SUFFIXES)
            or name.endswith(".index.json")  # multi-shard weight index
            or name == "config.json"
        ):
            continue
        shutil.copy2(p, os.path.join(out_dir, name))


def load_config(shards_dir: str) -> ModelConfig:
    with open(os.path.join(shards_dir, "config.json")) as f:
        return ModelConfig.from_json(f.read())


def load_tokenizer(shards_dir: str):
    """Load the HF tokenizer copied into a shard store, or None if the store
    carries no tokenizer files (or transformers can't load them). The ONE
    tokenizer-discovery rule shared by every engine/daemon construction
    path."""
    if not any(f.startswith("tokenizer") for f in os.listdir(shards_dir)):
        return None
    try:
        from transformers import AutoTokenizer

        return AutoTokenizer.from_pretrained(shards_dir)
    except Exception:  # noqa: BLE001 — tokenizer is an optional extra
        return None


def load_block(shards_dir: str, i: int, dtype=jnp.bfloat16) -> dict[str, Any]:
    """Decoder layer ``i`` of a store, on the host."""
    return _load_npz(os.path.join(shards_dir, f"block_{i}.npz"), dtype)


def load_stage(
    shards_dir: str,
    start: int,
    end: int,
    dtype=jnp.bfloat16,
    user_facing: Optional[bool] = None,
    pad_to: Optional[int] = None,
) -> dict[str, Any]:
    """Load one pipeline stage's params for layers ``[start, end)``.

    Role conditionals mirror ``NodeWorker.load_shards``
    (``/root/reference/utils/node_worker.py:127-185``): embedding iff
    ``user_facing`` (default: ``start == 0``), final norm + lm_head iff
    ``end == num_hidden_layers``.

    ``pad_to`` pads the stacked layer arrays (and returns ``layer_mask``) so
    ragged stages share one SPMD program shape.
    """
    cfg = load_config(shards_dir)
    L = cfg.num_hidden_layers
    if not (0 <= start < end <= L):
        raise ValueError(f"invalid layer range [{start}, {end}) for {L}-layer model")
    if user_facing is None:
        user_facing = start == 0

    blocks = [load_block(shards_dir, i, dtype) for i in range(start, end)]
    n = end - start
    pad_to = pad_to or n
    if pad_to < n:
        raise ValueError(f"pad_to={pad_to} < stage size {n}")
    kinds = cfg.layer_kinds[start:end]
    if kinds and pad_to > n:
        raise NotImplementedError(
            f"pad_to over a model with layers of several kinds "
            f"({cfg.model_type}): load the stage unpadded; the engine pads "
            "each kind's stack (parallel/placement.stack_stage_params)"
        )
    if pad_to > n:
        pad_block = jax.tree.map(np.zeros_like, blocks[0])
        blocks = blocks + [pad_block] * (pad_to - n)
    # stacks through QTensor leaves (q and scale stacked independently) —
    # on the host, like everything this loader returns
    stack = lambda some: jax.tree.map(lambda *xs: np.stack(xs), *some)
    if kinds:  # one stack per kind, in layer order
        stacked = {
            kind: stack([b for b, k in zip(blocks, kinds) if k == kind])
            for kind in dict.fromkeys(kinds)
        }
    else:
        stacked = stack(blocks)

    stage: dict[str, Any] = {
        "layers": stacked,
        "layer_mask": np.arange(pad_to) < n,
        "start": start,
        "end": end,
    }
    if user_facing:
        stage.update(_load_npz(os.path.join(shards_dir, "embedding.npz"), dtype))
    if end == L:
        stage.update(_load_npz(os.path.join(shards_dir, "final_norm.npz"), dtype))
        head_path = os.path.join(shards_dir, "lm_head.npz")
        if os.path.exists(head_path):
            stage.update(_load_npz(head_path, dtype))
        elif "embed" not in stage:
            # tied model: the last stage projects against the embedding table
            stage["embed"] = _load_npz(
                os.path.join(shards_dir, "embedding.npz"), dtype
            )["embed"]
    return stage


def load_full(shards_dir: str, dtype=jnp.bfloat16) -> tuple[ModelConfig, dict]:
    """Load the whole model onto the HOST (≙ ``inference.py``'s load). The
    engines place it from there: ``PipelineEngine`` stage by stage, a
    monolithic caller with its own ``jax.device_put``."""
    cfg = load_config(shards_dir)
    stage = load_stage(shards_dir, 0, cfg.num_hidden_layers, dtype, user_facing=True)
    params = {k: v for k, v in stage.items() if k not in ("layer_mask", "start", "end")}
    return cfg, params


def convert_hf_checkpoint(
    model_dir: str,
    out_dir: str,
    dtype=jnp.bfloat16,
    quantize: bool = False,
    quantize_head: bool = False,
    quant_bits: int = 8,
) -> ModelConfig:
    """Offline conversion entry (≙ running ``ModelSharder`` as a script,
    ``/root/reference/utils/model_sharder.py:137-145``; ``quantize`` ≙ its
    int8 mode, ``:28-45``).

    Reads HF ``config.json`` + ``*.safetensors`` (or torch ``*.bin``) from
    ``model_dir``, streams tensors, writes the shard store to ``out_dir``.
    """
    with open(os.path.join(model_dir, "config.json")) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))

    st_files = sorted(
        f for f in os.listdir(model_dir) if f.endswith(".safetensors")
    )
    handles: list[Any] = []
    if st_files:
        from safetensors import safe_open

        # name → open handle; safe_open.get_tensor reads ONE tensor at a time,
        # which is what keeps conversion memory at ~one-layer scale (the
        # streaming contract in the module docstring). Handles are tracked and
        # closed in the finally below — one leaked fd per shard file adds up on
        # large multi-shard checkpoints.
        index: dict[str, Any] = {}
        for fn in st_files:
            handle = safe_open(os.path.join(model_dir, fn), framework="numpy")
            handles.append(handle)
            for name in handle.keys():
                index[name] = handle

        def get(name: str) -> np.ndarray:
            if name not in index:
                raise KeyError(name)
            return index[name].get_tensor(name)

    else:
        bins = sorted(f for f in os.listdir(model_dir) if f.endswith(".bin"))
        if not bins:
            raise FileNotFoundError(f"no safetensors/bin weights in {model_dir}")
        import torch

        sd: dict[str, np.ndarray] = {}
        for fn in bins:
            part = torch.load(
                os.path.join(model_dir, fn), map_location="cpu", weights_only=True
            )
            sd.update({k: v.float().numpy() for k, v in part.items()})

        def get(name: str) -> np.ndarray:
            return sd[name]

    try:
        save_shards_streaming(
            cfg, get, out_dir, dtype, tokenizer_dir=model_dir,
            quantize=quantize, quantize_head=quantize_head,
            quant_bits=quant_bits,
        )
    finally:
        for h in handles:
            close = getattr(h, "close", None)
            if close is not None:
                close()
    return cfg
