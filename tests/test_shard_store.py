"""Shard store roundtrip + role-conditional stage loading
(≙ ``ModelSharder.save_shards`` → ``NodeWorker.load_shards``,
``/root/reference/utils/model_sharder.py:48-134`` /
``utils/node_worker.py:127-185``)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.cache import init_cache
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.utils import shard_store

CFG = tiny_llama()


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    params = llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    out = str(tmp_path_factory.mktemp("shards") / "tiny_float32")
    shard_store.save_shards(CFG, params, out)
    return out, params


def test_full_roundtrip(store):
    out, params = store
    cfg2, loaded = shard_store.load_full(out, dtype=jnp.float32)
    assert cfg2 == CFG
    for key in ("embed", "final_norm", "lm_head"):
        np.testing.assert_array_equal(np.asarray(loaded[key]), np.asarray(params[key]))
    for k, v in params["layers"].items():
        np.testing.assert_array_equal(np.asarray(loaded["layers"][k]), np.asarray(v))


def test_no_file_exceeds_the_cap_and_split_store_loads_equal(
    store, tmp_path, monkeypatch
):
    """Units bigger than ``MAX_FILE_BYTES`` continue in ``.part<j>.npz``
    files (arrays bigger than a file cut along their leading axis) and load
    back bit-equal, quantized leaves and bf16 views included."""
    import os

    from llm_sharding_tpu.ops.quant import QTensor, quantize_params

    _, params = store
    cap = 8 << 10
    for name, tree, dtype in (
        ("f32", params, jnp.float32),
        ("q8", quantize_params(params, quantize_head=True), jnp.float32),
        ("bf16", jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
         jnp.bfloat16),
    ):
        whole, split = str(tmp_path / f"{name}_whole"), str(tmp_path / name)
        shard_store.save_shards(CFG, tree, whole)
        monkeypatch.setattr(shard_store, "MAX_FILE_BYTES", cap)
        shard_store.save_shards(CFG, tree, split)
        monkeypatch.undo()
        sizes = [os.path.getsize(os.path.join(split, f))
                 for f in os.listdir(split) if f.endswith(".npz")]
        assert len(sizes) > len(os.listdir(whole))
        assert max(sizes) < cap + 4096  # array bytes + npy/zip headers
        a = shard_store.load_full(split, dtype=dtype)[1]
        b = shard_store.load_full(whole, dtype=dtype)[1]
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert jax.tree.structure(a) == jax.tree.structure(b)
        for x, y in zip(la, lb):
            assert type(x) is type(y) and x.dtype == y.dtype
            np.testing.assert_array_equal(
                np.asarray(x).view(np.uint8), np.asarray(y).view(np.uint8)
            )
        if name == "q8":
            assert isinstance(a["layers"]["wq"], QTensor)


def test_role_conditional_loading(store):
    out, _ = store
    L = CFG.num_hidden_layers

    first = shard_store.load_stage(out, 0, 2, dtype=jnp.float32)
    assert "embed" in first and "lm_head" not in first

    mid = shard_store.load_stage(out, 2, 3, dtype=jnp.float32)
    assert "embed" not in mid and "lm_head" not in mid

    last = shard_store.load_stage(out, 3, L, dtype=jnp.float32)
    assert "lm_head" in last and "final_norm" in last and "embed" not in last

    # user_facing override: any node may hold the embedding for request
    # injection (≙ can_receive_user_request, node_worker.py:105-107)
    inj = shard_store.load_stage(out, 2, 3, dtype=jnp.float32, user_facing=True)
    assert "embed" in inj


def test_invalid_range_rejected(store):
    out, _ = store
    with pytest.raises(ValueError, match="invalid layer range"):
        shard_store.load_stage(out, 3, 2)
    with pytest.raises(ValueError, match="invalid layer range"):
        shard_store.load_stage(out, 0, CFG.num_hidden_layers + 1)


def test_padded_stage_equals_unpadded(store):
    """pad_to + layer_mask: a ragged stage padded to the SPMD shape computes
    the same function (SURVEY.md §7 'uneven layer splits')."""
    out, params = store
    B, S = 1, 6
    ids = jnp.arange(S, dtype=jnp.int32)[None]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = llama.embed(params, ids)

    plain = shard_store.load_stage(out, 1, 3, dtype=jnp.float32)
    padded = shard_store.load_stage(out, 1, 3, dtype=jnp.float32, pad_to=4)
    assert padded["layers"]["wq"].shape[0] == 4
    assert list(np.asarray(padded["layer_mask"])) == [True, True, False, False]

    c1 = init_cache(CFG, B, S, num_layers=2, dtype=jnp.float32)
    h1, _, _ = llama.forward_layers(CFG, plain["layers"], h, c1, positions)
    c2 = init_cache(CFG, B, S, num_layers=4, dtype=jnp.float32)
    h2, _, _ = llama.forward_layers(
        CFG, padded["layers"], h, c2, positions, layer_mask=padded["layer_mask"]
    )
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-6)


def test_streaming_save_matches_hf_layout(tmp_path):
    """save_shards_streaming from an HF-style name→tensor dict must produce a
    store the stage loader can consume."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(1)
    hf_cfg = LlamaConfig(
        vocab_size=CFG.vocab_size,
        hidden_size=CFG.hidden_size,
        intermediate_size=CFG.intermediate_size,
        num_hidden_layers=CFG.num_hidden_layers,
        num_attention_heads=CFG.num_attention_heads,
        num_key_value_heads=CFG.num_key_value_heads,
        tie_word_embeddings=False,
    )
    m = LlamaForCausalLM(hf_cfg)
    sd = {k: v.detach().numpy() for k, v in m.state_dict().items()}

    out = str(tmp_path / "hf_tiny")
    shard_store.save_shards_streaming(CFG, sd, out, dtype=jnp.float32)
    cfg2, loaded = shard_store.load_full(out, dtype=jnp.float32)

    from llm_sharding_tpu.utils.convert import params_from_hf

    direct = params_from_hf(CFG, sd, dtype=jnp.float32)
    for k in ("embed", "final_norm", "lm_head"):
        np.testing.assert_array_equal(np.asarray(loaded[k]), np.asarray(direct[k]))
    for k in direct["layers"]:
        np.testing.assert_array_equal(
            np.asarray(loaded["layers"][k]), np.asarray(direct["layers"][k])
        )


def test_bf16_store_round_trip(tmp_path):
    """npz cannot natively round-trip ml_dtypes bf16 (saved as raw void, no
    cast back) — the store writes integer views + a dtype tag instead. A
    bf16-saved store must load back bitwise in bf16 and upcast to f32."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from llm_sharding_tpu.models import llama
    from llm_sharding_tpu.models.config import tiny_llama
    from llm_sharding_tpu.utils import shard_store

    cfg = tiny_llama(num_hidden_layers=2)
    params = llama.init_params(cfg, jax.random.key(1), dtype=jnp.bfloat16)
    out = str(tmp_path / "bf16_store")
    shard_store.save_shards(cfg, params, out)

    cfg2, loaded = shard_store.load_full(out, dtype=jnp.bfloat16)
    assert cfg2 == cfg
    np.testing.assert_array_equal(
        np.asarray(loaded["embed"]).view(np.uint16),
        np.asarray(params["embed"]).view(np.uint16),
    )
    _, as_f32 = shard_store.load_full(out, dtype=jnp.float32)
    assert as_f32["layers"]["wq"].dtype == jnp.float32
