"""The options of a server are written once (``runtime/options.py``): the
same refusal from ``engine.serve`` and from the CLI before it loads a model,
a snapshot that carries every portable field and drops the retired ones, a
re-shard that rebuilds from the live server's record."""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_sharding_tpu import cli
from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.generate import generate
from llm_sharding_tpu.runtime.options import RETIRED, ServeOptions
from llm_sharding_tpu.runtime.server import (
    PipelineServer, load_snapshot, save_snapshot,
)

# (an end-of-text id the vocabulary cannot emit: requests run to their budget)
CFG = tiny_llama(num_hidden_layers=4, eos_token_id=256)
PAGED = dict(kv_block_size=8, kv_blocks=33)


@pytest.fixture(scope="module")
def setup():
    params = llama.init_params(CFG, jax.random.key(48), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=2, devices=jax.devices()[:2],
                         cache_dtype=jnp.float32)
    return params, eng


def oracle(params, p, n):
    res = generate(CFG, params, p, n, cache_dtype=jnp.float32)
    return [int(t) for t in res.tokens[0, len(p): int(res.lengths[0])]]


def prompt(seed, n=5):
    return np.random.default_rng(seed).integers(1, 250, n).astype(np.int32)


def flags(kw) -> list:
    """The serve flags that ask for ``kw``."""
    dest = dict(cli._serve_flags())
    return [a for k, v in kw.items()
            for a in ("--" + dest[k].replace("_", "-"), str(v))]


@pytest.mark.parametrize("kw", [
    dict(kv_block_size=16),
    dict(kv_block_size=12, kv_blocks=8),
    dict(kv_block_size=16, kv_blocks=1),
    dict(kv_dtype="int8"),
    dict(paged_attn="xla"),
    dict(prefix_cache="hbm"),
    dict(PAGED, prefix_cache="hbm", host_pool_blocks=8),
    dict(PAGED, prefix_cache="disk"),
    dict(PAGED, disk_pool_blocks=4),
    dict(prefill_chunk=12),
    dict(speculate=-1),
    dict(max_queue=-1),
    dict(default_deadline_s=-1.0),
    dict(snapshot_every_s=1.0),
    dict(cp=0),
    dict(cp=2),
    dict(PAGED, cp=2, prefix_cache="hbm"),
], ids=lambda kw: ",".join(f"{k}={v}" for k, v in kw.items()))
def test_invalid_options_refused(setup, capsys, kw):
    """An inconsistent set is refused by ``engine.serve`` and by the CLI —
    there before any model is read: the store does not exist — in the same
    words."""
    _, eng = setup
    with pytest.raises(ValueError) as refusal:
        eng.serve(**kw)
    assert cli.main(["serve", "/no/such/store", *flags(kw)]) == 2
    err = capsys.readouterr().err
    assert f"error: {refusal.value}" in err
    # ... followed by the flags of the options the refusal names
    assert any(f"(flags: {flag}" in err or f", {flag}" in err
               for flag in flags(kw)[::2])


def test_snapshot_carries_every_portable_field(setup):
    """``serve_kwargs`` are the record's portable fields as the server runs
    them — a later option is carried unless its field says otherwise."""
    _, eng = setup
    srv = eng.serve(capacity=64, prefix_cache="host", max_queue=7, **PAGED)
    kwargs = srv.snapshot()["serve_kwargs"]
    local = {
        "trace_path", "fault_plan", "fault_retries", "fault_backoff_s",
        "retryable_exceptions", "snapshot_every_s", "snapshot_path",
        "gauge_sweep_every_s",
    }
    assert set(kwargs) == set(ServeOptions.names()) - local
    assert not set(kwargs) & set(RETIRED)
    assert kwargs == {k: getattr(srv, k) for k in kwargs}
    # the host tier's default size is the arena's: the RESOLVED record rides
    assert kwargs["host_pool_blocks"] == PAGED["kv_blocks"]
    assert kwargs["max_queue"] == 7 and kwargs["capacity"] == 64
    srv.close()


@pytest.mark.parametrize("retired", [
    {"inflight_steps": 1}, {"inflight_steps": 4}, {"chunk_cycles": 1},
    # what the parent commit's format-8 snapshots hold
    {"chunk_cycles": 1, "inflight_steps": 1},
], ids=lambda kw: ",".join(f"{k}={v}" for k, v in kw.items()))
def test_restore_drops_retired_options(setup, tmp_path, retired):
    """A snapshot of a build that still had the options restores — they
    changed how the host stepped, never the tokens — and finishes
    token-exact."""
    params, eng = setup
    srv = eng.serve(capacity=64)
    ps = [prompt(s) for s in (11, 12)]
    reqs = [srv.submit(p, 10) for p in ps]
    for _ in range(3):
        srv.step()
    snap = srv.snapshot()
    snap["serve_kwargs"].update(retired)
    save_snapshot(snap, str(tmp_path / "s"))
    srv.close()
    srv2 = PipelineServer.restore(eng, load_snapshot(str(tmp_path / "s")))
    assert not set(srv2.snapshot()["serve_kwargs"]) & set(RETIRED)
    revived = {r.id: r for r in srv2._rows if r is not None}
    srv2.run_until_idle()
    for r, p in zip(reqs, ps):
        assert revived[r.id].tokens == oracle(params, p, 10)
    srv2.close()


def test_unknown_option_refused(setup):
    """No shim for what is gone: a retired keyword is the ``TypeError`` any
    unknown keyword is; a snapshot key that is neither an option nor retired
    is refused by name."""
    _, eng = setup
    with pytest.raises(TypeError, match="'inflight_steps'"):
        eng.serve(inflight_steps=2)
    with pytest.raises(TypeError, match="'chunk_cycles'"):
        eng.serve(chunk_cycles=1)
    srv = eng.serve(capacity=64)
    snap = srv.snapshot()
    srv.close()
    snap["serve_kwargs"]["turbo"] = True
    with pytest.raises(ValueError, match="turbo"):
        PipelineServer.restore(eng, snap)
    del snap["serve_kwargs"]["turbo"]
    # (a field that stays with its process is not a snapshot's to carry)
    snap["serve_kwargs"]["snapshot_path"] = "/tmp/x"
    with pytest.raises(ValueError, match="snapshot_path"):
        PipelineServer.restore(eng, snap)


def test_reshard_rebuild_keeps_the_live_record(setup, capsys, tmp_path):
    """``:placement`` rebuilds the server from the LIVE server's record: a
    daemon whose options never were on its command line (``--restore``)
    keeps them — options without a flag included."""
    params, _ = setup
    eng = PipelineEngine(CFG, params, num_stages=2, devices=jax.devices()[:2],
                         cache_dtype=jnp.float32)
    srv = eng.serve(capacity=64, batch_per_slot=2, prefix_cache="hbm",
                    max_queue=7, pipeline_depth=2, prefill_chunk=16, **PAGED)
    srv.enable_auto_snapshot(str(tmp_path / "auto"), 3600.0)
    args = argparse.Namespace(trace_path=None, capacity=1024)
    new = cli._serve_control(eng, srv, ":placement 0:1,1:4", args)
    assert "placement applied" in capsys.readouterr().err
    assert new is not srv and srv._closed
    assert new.options == srv.options
    assert new.snapshot_path == str(tmp_path / "auto")
    p = prompt(21)
    assert new.result(new.submit(p, 8)) == oracle(params, p, 8)
    new.close()
