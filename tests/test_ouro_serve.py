"""``ouro`` through the engine and the server on the CPU at tiny widths
(``tests/test_ouro.py`` holds the model to the equations written out by hand):
``PipelineEngine.serve()`` over an arena of ``T · L`` layer slots — chunked
admission in whole chunks, rows admitted at different times, freed and reused,
the radix cache, its host tier, snapshots, ``extract`` / ``adopt`` and the
disaggregated hand-off all carrying nine slots for three layers —, a server
without ``prefill_chunk`` (the one-shot dense window), one without pages, an
int8 arena, two tensor shards; the exit-pass counter against the hand-written
gate; the step programs' words; a one-pass model's decode program unchanged;
and every refusal by name."""

import dataclasses
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_ouro, tiny_qwen2
from llm_sharding_tpu.obs.metrics import REGISTRY
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.generate import generate

from test_ouro import CFG, TOL, hand_model, seeded_params, params  # noqa: F401

PAGED = dict(capacity=128, batch_per_slot=2, kv_block_size=8, kv_blocks=129)
SLOTS = CFG.passes * CFG.num_hidden_layers  # 9


def engine(params, cfg=CFG, **kw):
    kw.setdefault("devices", jax.devices()[:1])
    return PipelineEngine(cfg, params, num_stages=1, cache_dtype=jnp.float32,
                          **kw)


def oracle(params, prompt, n, cfg=CFG):
    res = generate(cfg, params, prompt, n, cache_dtype=jnp.float32)
    return list(res.tokens[0, len(prompt):int(res.lengths[0])])


def margins(params, prompt, served, cfg=CFG):
    """How far below the hand-written reference's best logit each served
    token lies, teacher-forced over prompt + served: the served path's
    LOGITS held to the equations, not only its tokens to the monolith's."""
    ids = np.concatenate([prompt, served])
    logits, at, _ = hand_model(cfg, params, ids)
    rows = np.asarray(logits)[len(prompt) - 1:len(ids) - 1]
    got = rows[np.arange(len(served)), np.asarray(served)]
    return rows.max(-1) - got, np.asarray(at)[len(prompt) - 1:len(ids) - 1]


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 250, size=n).astype(np.int32) for n in lengths]


def exit_counts():
    fam = REGISTRY.get("server_exit_pass_total")
    return {k[0]: c.value for k, c in fam.series()}


def test_serving_over_an_arena_of_passes_times_layers_slots(
        params, monkeypatch, tmp_path):
    """(i) The normal serve path, kernels interpreted: every prompt admits
    chunk by chunk (``serve_admit``'s dense window of nine slots is never
    built), three rows are admitted at different times over one slot of two, a
    repeated prompt hits the radix cache over blocks of nine slots, a snapshot
    restores and continues; the arena gauges read one slot's entry."""
    from llm_sharding_tpu.parallel import serve as serve_ops
    from llm_sharding_tpu.runtime.server import (
        PipelineServer, load_snapshot, save_snapshot,
    )

    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    admits = []
    orig = serve_ops.serve_admit
    monkeypatch.setattr(
        serve_ops, "serve_admit",
        lambda *a, **kw: admits.append(1) or orig(*a, **kw))
    eng = engine(params)
    srv = eng.serve(prefix_cache="hbm", prefill_chunk=16, **PAGED)
    assert srv.attn_impl == "interpret" and srv._bucket(5) == 16
    # [S, T · Lp, NB, kv heads, BS, head dim]
    assert srv.state.k.shape == srv.state.v.shape == (1, SLOTS, 129, 4, 8, 16)
    # ONE slot's entry: 2 x 4 heads x 16 x f32; a token holds SLOTS of them
    entry = REGISTRY.get("server_kv_entry_bytes").value
    assert entry == 2 * 4 * 16 * 4
    assert srv.arena_bytes_device == SLOTS * 129 * 8 * entry
    prompts = prompts_of(5, 20, 37)
    first = srv.submit(prompts[0], 8)
    for _ in range(3):  # the others arrive while the first decodes
        srv.step()
    reqs = [first] + [srv.submit(p, 8) for p in prompts[1:]]
    srv.run_until_idle()
    assert not admits
    for p, r in zip(prompts, reqs):
        assert list(r.tokens) == oracle(params, p, 8)
        m, _ = margins(params, p, list(r.tokens))
        assert m.max() <= TOL  # float32: the reference's best token, or a tie
    hits = REGISTRY.get("server_prefix_cache_hit_tokens_total")
    before = sum(c.value for _, c in hits.series())
    again = srv.submit(prompts[2], 8)
    srv.run_until_idle()
    assert list(again.tokens) == list(reqs[2].tokens)
    assert sum(c.value for _, c in hits.series()) > before
    srv._alloc.check(), srv._radix.check()
    long = srv.submit(prompts[1], 12)
    for _ in range(4):
        srv.step()
    save_snapshot(srv.snapshot(), str(tmp_path / "snap"))
    srv.close()
    back = PipelineServer.restore(eng, load_snapshot(str(tmp_path / "snap")))
    assert back.state.k.shape[1] == SLOTS
    revived = next(r for r in back._rows + list(back._queue)
                   if r is not None and r.id == long.id)
    back.run_until_idle()
    assert list(revived.tokens) == oracle(params, prompts[1], 12)
    back.close()


@pytest.mark.parametrize("theta", [1.0, 0.5])
def test_the_exit_pass_counter_sits_where_the_gate_says(
        params, monkeypatch, theta):
    """(j) ``server_exit_pass_total`` adds up to the tokens emitted, pass by
    pass where the hand-written gate puts the positions that predicted them;
    the step records carry the same counts."""
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    cfg = dataclasses.replace(CFG, exit_threshold=theta)
    before = exit_counts()
    srv = engine(params, cfg).serve(prefill_chunk=16, **PAGED)
    prompts = prompts_of(9, 21, seed=4)
    reqs = [srv.submit(p, 10) for p in prompts]
    srv.run_until_idle()
    recs = srv.stepline_snapshot(256)
    srv.close()
    want = np.zeros(cfg.passes, int)
    for p, r in zip(prompts, reqs):
        assert list(r.tokens) == oracle(params, p, 10, cfg)
        _, at = margins(params, p, list(r.tokens), cfg)
        want += np.bincount(at, minlength=cfg.passes)
    after = exit_counts()
    got = [int(after[str(t)] - before.get(str(t), 0))
           for t in range(cfg.passes)]
    assert got == list(want) and sum(got) == sum(len(r.tokens) for r in reqs)
    assert list(np.sum([r["exit_passes"] for r in recs
                        if r.get("exit_passes")], axis=0)) == got
    if theta == 1.0:
        assert got[:-1] == [0] * (cfg.passes - 1)
    else:
        assert sum(n > 0 for n in got) > 1


def test_without_a_prefill_chunk_without_pages_and_over_an_int8_arena(
        params):
    """The one-shot path (a dense window of nine slots cut into blocks), a
    server without pages (the dense state, nine rows of layers) and a
    quantised arena (scales for nine slots): each serves the monolith's
    tokens — the int8 arena's within a tie of them. (XLA attention: the
    kernels are the first test's.)"""
    eng = engine(params)
    prompt = prompts_of(11, seed=7)[0]
    want = oracle(params, prompt, 6)
    srv = eng.serve(prefix_cache="hbm", **PAGED)
    assert srv._bucket(5) == 8
    assert srv.result(srv.submit(prompt, 6)) == want
    srv.close()
    dense = eng.serve(capacity=64, batch_per_slot=2)
    assert dense.state.k.shape[:2] == (1, SLOTS)
    assert dense.result(dense.submit(prompt, 6)) == want
    dense.close()
    q = eng.serve(kv_dtype="int8", prefill_chunk=16, **PAGED)
    assert q.state.k_scale.shape[:2] == (1, SLOTS)
    got = q.result(q.submit(prompt, 6))
    q.close()
    m, _ = margins(params, prompt, got)
    assert m.max() < 0.2  # an int8 arena: near the best logit, not bit-exact


def test_extract_and_adopt_move_a_request_between_looped_arenas(
        params):
    servers = [
        engine(params, devices=jax.devices()[i:i + 1]).serve(
            prefix_cache="hbm", **dict(PAGED, kv_blocks=65))
        for i in (0, 1)
    ]
    prompt = prompts_of(9, seed=5)[0]
    req = servers[0].submit(prompt, 12)
    for _ in range(5):
        servers[0].step()
    assert req.tokens and not req.done
    servers[1].adopt(servers[0].extract(req), req)
    assert servers[1].result(req) == oracle(params, prompt, 12)
    for srv in servers:
        srv.close()


def test_the_host_tier_and_the_disaggregated_hand_off_carry_nine_slots(
        params):
    from llm_sharding_tpu.runtime.disagg import DisaggServer

    paged = dict(capacity=128, kv_block_size=8, kv_blocks=65,
                 prefill_chunk=16)
    prompt = prompts_of(40, seed=1)[0]
    want = oracle(params, prompt, 6)
    srv = engine(params).serve(batch_per_slot=2, prefix_cache="host",
                               host_pool_blocks=32, **paged)
    assert srv.result(srv.submit(prompt, 6)) == want
    srv._radix.demote_all()
    assert srv.result(srv.submit(prompt, 6)) == want
    assert srv.prefix_cache_stats()["host_hit_tokens"] == 32
    srv._alloc.check(), srv._radix.check()
    srv.close()

    ok = REGISTRY.get("server_disagg_handoffs_total")
    before = dict(ok.series()).get(("ok",))
    before = before.value if before else 0
    dis = DisaggServer(
        CFG, params, data_parallel=2, num_stages=1,
        devices=jax.devices()[:2], cache_dtype=jnp.float32,
        prefix_cache="hbm", roles=["prefill", "decode"], **paged)
    req = dis.submit(prompt, 6)
    dis.run_until_idle()
    assert list(req.tokens) == want
    assert dict(ok.series())[("ok",)].value == before + 1
    dis.close()


def test_two_tensor_shards_norm_after_the_psum(params):
    """Tensor parallelism rides ``attn_mlp_block``: the output norms sit
    after the row-parallel psums, their gains replicated, and the served
    tokens are the monolith's."""
    eng = PipelineEngine(CFG, params, num_stages=1, tensor_parallel=2,
                         cache_dtype=jnp.float32, devices=jax.devices()[:2])
    srv = eng.serve(prefill_chunk=16, **PAGED)
    assert srv.state.k.shape[1] == SLOTS
    prompt = prompts_of(19, seed=8)[0]
    got = srv.result(srv.submit(prompt, 6))
    srv.close()
    assert got == oracle(params, prompt, 6)


def _lowered(eng, names):
    """Serve one request with the kernels interpreted and lower each named
    step program with the very arguments the server dispatched it with."""
    from llm_sharding_tpu.parallel import serve as serve_ops

    lowered = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PAGED_FORCE_KERNEL", "interpret")
        for name in names:
            orig = getattr(serve_ops, name)

            def call(*a, _o=orig, _n=name, **kw):
                if _n not in lowered:
                    lowered[_n] = _o.lower(*a, **kw)
                return _o(*a, **kw)

            mp.setattr(serve_ops, name, call)
        srv = eng.serve(prefill_chunk=16, **PAGED)
        srv.submit(np.arange(5, 25, dtype=np.int32), 3)
        srv.run_until_idle()
        srv.close()
    return lowered


def test_the_step_programs_name_the_close_of_a_pass(params):
    """(k) The decode and the chunk program carry ``pass_close`` beside the
    dense llama block's words, and no word of another family's
    (``tests/test_paged_programs.py`` holds the one-pass models' programs to being
    WITHOUT it); the layer body is traced ONCE: one decode kernel in the
    program's text, not one a pass."""
    from llm_sharding_tpu.obs.stepline import SCOPES

    lowered = _lowered(engine(params), ("serve_chunk", "serve_prefill_chunk"))
    assert sorted(lowered) == ["serve_chunk", "serve_prefill_chunk"]
    assert "pass_close" in SCOPES
    for name, low in lowered.items():
        text = low.as_text(debug_info=True)
        paths = set(re.findall(r'loc\("([^"]+)"', text))
        found = {w for w in SCOPES
                 if any(re.search(rf"(^|/){w}(/|$)", p) for p in paths)}
        assert {"pass_close", "norm", "qkv", "rope", "attn", "o_proj", "mlp",
                "state"} <= found
        # a chunk scatters its tiles; a decode step's entry is stored inside
        # the interpreted ``paged_decode``, under ``attn`` (PR 61)
        assert ("kv_write" in found) == (name == "serve_prefill_chunk")
        assert not found & {"router", "moe", "absorb", "zero_expert", "ssm",
                            "ssm_proj", "ssm_x", "moe_latent", "kda",
                            "kda_proj", "conv", "indexer", "select",
                            "kv_take", "kv_put"}
        # the output norms are ``norm`` INSIDE their branch's word
        assert any(re.search(r"o_proj/norm(/|$)", p) for p in paths)
        assert any(re.search(r"mlp/norm(/|$)", p) for p in paths)
    text = lowered["serve_chunk"].as_text()
    assert len(re.findall(r"func\.func private @\w*paged_decode", text)) <= 1


def test_a_one_pass_models_decode_program_is_unchanged():
    """(m) For ``passes == 1`` no program gains an operand, an output or a
    column of the log: the plain llama-family model's lowered decode program
    takes what it took (its layers' twelve leaves, mask, four head leaves,
    state) and returns the state and a log one column a row wide."""
    cfg = tiny_qwen2()
    p = llama.init_params(cfg, jax.random.key(2), jnp.float32)
    low = _lowered(engine(p, cfg), ("serve_chunk",))["serve_chunk"]
    args, out = low.in_avals, low.out_info
    leaves = jax.tree.leaves(args)
    assert "exit_gate" not in p and "attn_out_norm" not in p["layers"]
    state_leaves = len(jax.tree.leaves(out[0]))
    # 12 layer leaves + the mask + embed / final_norm / lm_head + the state
    assert len(leaves) == 12 + 1 + 3 + state_leaves
    assert out[1].shape == (1, PAGED["batch_per_slot"])
    # ... and the looped model's log is the exit pass a row wider
    looped = _lowered(engine(seeded_params(CFG)),
                      ("serve_chunk",))["serve_chunk"]
    assert looped.out_info[1].shape == (1, 2 * PAGED["batch_per_slot"])


def test_what_a_looped_stack_cannot_do_is_refused_by_name(params):
    """(l) A ring of stages, speculative decoding, context parallelism and
    the interleaved schedule under ``passes > 1`` — each by name, before
    anything is computed as something else."""
    from llm_sharding_tpu.parallel.schedule import interleaved_generate

    with pytest.raises(NotImplementedError, match="ring of 2 stages"):
        PipelineEngine(CFG, params, num_stages=2, cache_dtype=jnp.float32,
                       devices=jax.devices()[:2])
    eng = engine(params)
    with pytest.raises(NotImplementedError, match="speculate over a looped"):
        eng.serve(speculate=2, **PAGED)
    with pytest.raises(NotImplementedError, match="cp over a looped"):
        eng.serve(cp=2, **PAGED)
    with pytest.raises(NotImplementedError, match="interleaved schedule"):
        interleaved_generate(
            CFG, eng.mesh, eng.stage_layers, eng.layer_masks,
            eng.head_params, np.zeros((2, 4), np.int32), 2)
    # ... while the sequential pipeline on one stage is the monolith
    prompt = prompts_of(7, seed=3)[0]
    res = eng.generate_ids(prompt, 5)
    assert list(res.tokens[0, 7:int(res.lengths[0])]) == oracle(
        params, prompt, 5)
