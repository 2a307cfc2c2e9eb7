"""Flash-style chunked prefill over the paged arena (ISSUE 14).

The contract under test: chunked admission ATTENDS THE ARENA IN PLACE
(``ops/paged_attention.paged_prefill`` — no gathered-window round trip)
and is token-identical to the monolithic oracle AND across backends
(interpret-emulated kernel vs the exact XLA gather) on plain, quantized
and radix-hit workloads; a radix hit whose leftover suffix needs chunked
prefill ADMITS through it with a prefix offset instead of falling back
cold (the old one-shot-only restriction — the regression test here);
and the decode kernel's ``blocks_per_step`` batching is bit-identical
to the single-block grid.

``PAGED_TEST_BLOCK_SIZE`` parameterizes the block size (CI reruns at 4:
block-boundary stress — chunks straddle block seams) and
``PAGED_FORCE_KERNEL=interpret`` drives the whole suite through the
chunked-prefill kernel code path on the CPU mesh.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.ops.paged_attention import (
    auto_blocks_per_step, paged_attention_tpu, paged_attention_xla,
    paged_prefill, paged_prefill_tpu,
)
from llm_sharding_tpu.ops.quant import kv_qmax, kv_quantize
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.generate import generate

CFG = tiny_llama(num_hidden_layers=8, max_position_embeddings=512)
BS = int(os.environ.get("PAGED_TEST_BLOCK_SIZE", "8"))
CAP = 256
CHUNK = 16


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG, jax.random.key(11), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=4, cache_dtype=jnp.float32)
    return params, eng


def oracle(params, p, n, **kw):
    res = generate(CFG, params, p, n, cache_dtype=jnp.float32, **kw)
    return [int(x) for x in res.tokens[0, len(p): int(res.lengths[0])]]


def serve(eng, **kw):
    kw.setdefault("capacity", CAP)
    kw.setdefault("kv_block_size", BS)
    kw.setdefault("kv_blocks", 4 * CAP // BS + 1)
    kw.setdefault("prefill_chunk", CHUNK)
    return eng.serve(**kw)


def prompt(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n
    ).astype(np.int32)


def drive(srv, reqs):
    while any(not r.done for r in reqs):
        srv.step()
    return [list(r.tokens) for r in reqs]


# ---------------------------------------------------------------- op level


def _op_case(seed=0, S=12, T=8, sentinel_from=20, layer=2):
    """The ops' positional arguments ``(q, k_stack, v_stack, layer, table,
    q_positions, kv_positions)`` over a head-major stack of three layers,
    every one different, attended at ``layer``."""
    rng = np.random.default_rng(seed)
    L, Nkv, G, D, NB = 3, 2, 2, 16, 24
    bs = 4
    W = T * bs
    ka = jnp.asarray(rng.normal(size=(L, NB, Nkv, bs, D)).astype(np.float32))
    va = jnp.asarray(rng.normal(size=(L, NB, Nkv, bs, D)).astype(np.float32))
    tbl = jnp.asarray(rng.integers(1, NB, (2, T)).astype(np.int32))
    tbl = tbl.at[0, T - 2:].set(0)  # trash tail on row 0
    kvpos = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None], (2, W))
    kvpos = jnp.where(kvpos < sentinel_from, kvpos, jnp.int32(2**30))
    q = jnp.asarray(
        rng.normal(size=(2, S, Nkv * G, D)).astype(np.float32)
    )
    qp = jnp.broadcast_to(
        jnp.arange(8, 8 + S, dtype=jnp.int32)[None], (2, S)
    )
    return q, ka, va, layer, tbl, qp, kvpos


def test_paged_prefill_interpret_matches_xla_all_bps():
    args = _op_case()
    ref = paged_attention_xla(*args)
    for bps in (1, 2, 4):
        out = paged_prefill_tpu(*args, interpret=True, blocks_per_step=bps)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_paged_prefill_nlive_clamp_is_inert():
    # nlive covering the written frontier (20 cols / bs=4 -> 5 blocks)
    # must not change the result: everything past it is sentinel-masked
    args = _op_case()
    ref = paged_attention_xla(*args)
    out = paged_prefill_tpu(
        *args, interpret=True,
        nlive=jnp.asarray([5, 5], jnp.int32), blocks_per_step=2,
    )
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_paged_prefill_quantized_fused_dequant():
    q, ka, va, *rest = _op_case(seed=3)
    sk = jnp.max(jnp.abs(ka), axis=(3, 4)) / kv_qmax(jnp.int8)  # [L,NB,Nkv]
    sv = jnp.max(jnp.abs(va), axis=(3, 4)) / kv_qmax(jnp.int8)
    kq = kv_quantize(ka, sk[..., None, None], jnp.int8)
    vq = kv_quantize(va, sv[..., None, None], jnp.int8)
    ref = paged_attention_xla(q, kq, vq, *rest, k_scale=sk, v_scale=sv)
    out = paged_prefill_tpu(
        q, kq, vq, *rest, interpret=True,
        k_scale=sk, v_scale=sv, blocks_per_step=2,
    )
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_decode_blocks_per_step_matches_single_block():
    q, ka, va, lyr, tbl, qp, kvpos = _op_case(S=1, sentinel_from=32)
    args = (q[:, :1], ka, va, lyr, tbl, qp[:, :1], kvpos)
    ref = paged_attention_xla(*args)
    for bps in (1, 2, 4, 8):
        out = paged_attention_tpu(
            *args, interpret=True, blocks_per_step=bps,
        )
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_auto_blocks_per_step():
    assert auto_blocks_per_step(8, 4) == 8
    assert auto_blocks_per_step(7, 4) == 1  # must divide the table width
    assert auto_blocks_per_step(64, 64) == 8
    assert auto_blocks_per_step(64, 512) == 1  # tile cap
    assert auto_blocks_per_step(6, 8) == 2
    # the decode kernel takes a block's key/value heads together: the
    # step's score tile is bps x block_size x heads lanes wide
    assert auto_blocks_per_step(128, 32, 4) == 8  # Qwen2.5-7B
    assert auto_blocks_per_step(128, 32, 8) == 8  # a Qwen2.5-14B stage
    assert auto_blocks_per_step(128, 32, 16) == 4  # OLMoE-1B-7B
    assert auto_blocks_per_step(128, 128, 32) == 1


def test_paged_prefill_backend_validation():
    args = _op_case()
    with pytest.raises(ValueError, match="expected one of"):
        paged_prefill(*args, backend="bogus")
    if jax.default_backend() != "tpu":
        with pytest.raises(ValueError, match="requires a TPU backend"):
            paged_prefill(*args, backend="kernel")


# ------------------------------------------------------------- serve level


def test_chunked_prefill_offset0_matches_oracle(setup):
    """Cold chunked admission (offset == 0 equivalence) through the
    arena-native path, chunks straddling block seams at every
    PAGED_TEST_BLOCK_SIZE."""
    params, eng = setup
    srv = serve(eng)
    # 56 tokens: bucket 64 = 4 chunks; at BS=4 each chunk covers 4
    # blocks, at BS=8 a chunk spans 2 — both straddle seams
    ps = [prompt(7, 56), prompt(8, 23)]  # 23: prompt ends mid-block
    reqs = [srv.submit(p, max_new_tokens=6) for p in ps]
    toks = drive(srv, reqs)
    for p, t in zip(ps, toks):
        assert t == oracle(params, p, 6)
    srv.close()


def test_chunked_prefill_interpret_matches_xla_server(setup, monkeypatch):
    """The acceptance oracle: the SAME chunked workload through the
    interpret-emulated kernel vs the exact XLA gather backend — token
    match must be 1.0."""
    params, eng = setup
    ps = [prompt(17, 56), prompt(18, 40)]

    def run(force):
        if force:
            monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
        else:
            monkeypatch.delenv("PAGED_FORCE_KERNEL", raising=False)
        srv = serve(eng, paged_attn="auto" if force else "xla")
        assert srv.attn_impl == ("interpret" if force else "xla")
        toks = drive(srv, [srv.submit(p, max_new_tokens=6) for p in ps])
        srv.close()
        return toks

    assert run(True) == run(False)


def test_radix_hit_long_suffix_admits_chunked(setup, monkeypatch):
    """THE regression test: a radix hit whose leftover suffix needs
    chunked admission used to fall back cold (zero hit tokens); now it
    admits through serve_prefill_chunk at the hit's prefix offset,
    token-identically. The shared prefix deliberately ends MID-BLOCK
    (43 tokens) so the match rounds down to a block boundary."""
    params, eng = setup
    import llm_sharding_tpu.runtime.server as server_mod

    srv = serve(eng, prefix_cache="hbm")
    shared = prompt(21, 43)  # match will round down to (43 // BS) * BS
    p1 = np.concatenate([shared, prompt(22, 9)])
    r1 = srv.submit(p1, max_new_tokens=6)
    drive(srv, [r1])
    assert r1.tokens == oracle(params, p1, 6)

    offs = []
    orig = server_mod.PipelineServer._admit_chunked

    def spy(self, *a, **kw):
        offs.append(kw.get("prefix_off", 0))
        return orig(self, *a, **kw)

    monkeypatch.setattr(server_mod.PipelineServer, "_admit_chunked", spy)
    hit0 = srv._radix.hit_tokens
    # long suffix: bucket(suffix) > prefill_chunk -> needs chunked
    p2 = np.concatenate([shared, prompt(23, 60)])
    r2 = srv.submit(p2, max_new_tokens=6)
    drive(srv, [r2])
    expect_n = (43 // BS) * BS
    assert srv._radix.hit_tokens - hit0 == expect_n, (
        "radix hit with a chunked suffix fell back cold"
    )
    assert offs == [expect_n], (
        "hit did not admit through chunked prefill at its offset"
    )
    assert r2.tokens == oracle(params, p2, 6)
    # the finished chunked row's prompt blocks insert back into the tree
    # (minus the injected final token's block) and a full repeat still
    # serves correctly
    r3 = srv.submit(p2, max_new_tokens=6)
    drive(srv, [r3])
    assert r3.tokens == oracle(params, p2, 6)
    srv._alloc.check()
    srv._radix.check()
    srv.close()


def test_radix_chunked_quantized_token_match(setup):
    """Quantized (int8) chunked admission over a radix hit: the arena-
    native path quantizes fresh chunk KV at insert (no inter-chunk
    dequant round trip) and never rewrites the shared prefix blocks.
    int8 greedy may drift from the f32 oracle (the kv-quant tolerance
    harness owns that); here the contract is internal consistency:
    warm == cold int8 output."""
    params, eng = setup
    shared = prompt(31, 2 * BS)
    p = np.concatenate([shared, prompt(32, 60)])

    def run(cache):
        srv = serve(eng, prefix_cache=cache, kv_dtype="int8")
        if cache != "off":
            rw = srv.submit(np.concatenate([shared, prompt(33, 5)]), 4)
            drive(srv, [rw])  # warm the tree
            hit0 = srv._radix.hit_tokens
        r = srv.submit(p, max_new_tokens=6)
        drive(srv, [r])
        if cache != "off":
            assert srv._radix.hit_tokens - hit0 == 2 * BS
        srv.close()
        return r.tokens

    assert run("hbm") == run("off")


def test_prefill_path_metrics(setup):
    from llm_sharding_tpu.obs.metrics import (
        PREFILL_BLOCKS_READ, PREFILL_PATH,
    )

    params, eng = setup
    srv = serve(eng)
    b0 = PREFILL_BLOCKS_READ.value
    r = srv.submit(prompt(41, 56), max_new_tokens=4)
    drive(srv, [r])
    # bucket 64 in 4 chunks of 16: frontier blocks per chunk summed
    expect = sum(-(-(off + CHUNK) // BS) for off in range(0, 64, CHUNK))
    assert PREFILL_BLOCKS_READ.value - b0 == expect
    # xla resolution on the CPU mesh (or kernel under the interpret lane)
    want = (
        "kernel" if os.environ.get("PAGED_FORCE_KERNEL") == "interpret"
        else "xla"
    )
    vals = {
        p: PREFILL_PATH.labels(path=p).value
        for p in ("kernel", "xla", "gather")
    }
    assert vals[want] == 1.0
    assert sum(vals.values()) == 1.0
    srv.close()


def test_chunked_prefill_under_live_decode(setup):
    """A chunked admission landing while another slot is mid-decode:
    the interleaved decode cycles (whose parked-slot writes are now
    gated) must neither corrupt the admitting slot nor the live one."""
    params, eng = setup
    srv = serve(eng)
    bg = srv.submit(prompt(51, 6), max_new_tokens=24)
    while not bg.tokens:
        srv.step()
    long_r = srv.submit(prompt(52, 56), max_new_tokens=6)
    toks = drive(srv, [bg, long_r])
    assert toks[0] == oracle(params, prompt(51, 6), 24)
    assert toks[1] == oracle(params, prompt(52, 56), 6)
    srv.close()
