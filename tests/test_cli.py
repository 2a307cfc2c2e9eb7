"""Operator CLI: a shell user can generate, serve, and profile without
writing Python (VERDICT r1 missing #1 / next-round #4; ≙ the reference's
entry scripts ``start_node.py`` / ``send_config.py`` / ``profiling.py`` /
``inference.py``)."""

import io
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu import cli
from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.utils import shard_store

CFG = tiny_llama(num_hidden_layers=8, vocab_size=64)


class IdTokenizer:
    """Minimal tokenizer standing in for HF AutoTokenizer in CLI tests."""

    def __call__(self, text):
        return {"input_ids": [ord(c) % 60 + 1 for c in text]}

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(int(i) % 26 + 97) for i in ids)


@pytest.fixture(scope="module")
def shards(tmp_path_factory):
    params = llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    out = str(tmp_path_factory.mktemp("cli") / "tiny_f32")
    shard_store.save_shards(CFG, params, out)
    return out


def test_generate_command(shards, capsys, monkeypatch):
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    rc = cli.main(
        [
            "generate", shards, "--prompt", "hello", "--max-new", "6",
            "--stages", "4", "--dtype", "f32",
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out.strip()
    assert len(out) > 0


def test_generate_ragged_ranges_stream(shards, capsys, monkeypatch):
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    rc = cli.main(
        [
            "generate", shards, "--prompt", "abc", "--max-new", "5",
            "--ranges", "0:5,5:6,6:8", "--dtype", "f32", "--stream",
        ]
    )
    assert rc == 0
    assert len(capsys.readouterr().out.strip()) > 0


def test_serve_command_stdin(shards, capsys, monkeypatch):
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    monkeypatch.setattr("sys.stdin", io.StringIO("hi there\nsecond prompt\n"))
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "4",
            "--capacity", "64", "--dtype", "f32",
        ]
    )
    assert rc == 0
    captured = capsys.readouterr()
    # two prompts -> two completion lines on stdout, counters on stderr
    assert len([l for l in captured.out.splitlines() if l.strip()]) == 2
    assert '"requests_completed": 2' in captured.err


def test_serve_command_tensor_parallel(shards, capsys, monkeypatch):
    """--tensor-parallel: the daemon serves over a pp×tp mesh (2 stages × 2
    tensor shards on 4 devices)."""
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    monkeypatch.setattr("sys.stdin", io.StringIO("hi there\n"))
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "2",
            "--tensor-parallel", "2", "--capacity", "64", "--dtype", "f32",
        ]
    )
    assert rc == 0
    captured = capsys.readouterr()
    assert len([l for l in captured.out.splitlines() if l.strip()]) == 1
    assert '"requests_completed": 1' in captured.err


def test_serve_snapshot_restore_cli(shards, tmp_path, capsys, monkeypatch):
    """:snapshot DIR writes a live-daemon checkpoint; serve --restore DIR
    resumes it and keeps serving new prompts."""
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    d = str(tmp_path / "snap")
    monkeypatch.setattr(
        "sys.stdin", io.StringIO(f"first prompt\n:snapshot {d}\n")
    )
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "4",
            "--capacity", "64", "--dtype", "f32",
        ]
    )
    assert rc == 0
    err = capsys.readouterr().err
    assert f"snapshot written to {d}" in err

    monkeypatch.setattr("sys.stdin", io.StringIO("after restore\n"))
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "4",
            "--capacity", "64", "--dtype", "f32", "--restore", d,
        ]
    )
    assert rc == 0
    captured = capsys.readouterr()
    assert "restored snapshot" in captured.err
    assert '"requests_completed": 2' in captured.err  # 1 restored + 1 new
    assert len([l for l in captured.out.splitlines() if l.strip()]) == 1


def test_serve_restore_banner_reports_snapshot_flags(
    shards, tmp_path, capsys, monkeypatch
):
    """--restore with serve flags that differ from the snapshot: the banner
    must report the capacity the daemon ACTUALLY runs at (the snapshot's)
    and warn that the differing CLI flags are ignored (ADVICE r5 — the old
    banner printed args.capacity while serve_kwargs silently won)."""
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    d = str(tmp_path / "snap2")
    monkeypatch.setattr(
        "sys.stdin", io.StringIO(f"one prompt\n:snapshot {d}\n")
    )
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "4",
            "--capacity", "64", "--dtype", "f32",
        ]
    )
    assert rc == 0
    capsys.readouterr()

    monkeypatch.setattr("sys.stdin", io.StringIO(""))
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "4",
            "--capacity", "128", "--dtype", "f32", "--restore", d,
        ]
    )
    assert rc == 0
    err = capsys.readouterr().err
    assert "capacity=64" in err  # the snapshot's, not the CLI's 128
    assert "capacity=128" not in err.replace("--capacity 128", "")
    assert "ignored" in err and "--capacity 128" in err


def test_serve_kv_flag_pairing_fast_fails(shards, capsys):
    """An unpaired --kv-block-size/--kv-blocks fails in milliseconds,
    BEFORE model load (same pre-load pattern as the snapshot flag pair)."""
    rc = cli.main(["serve", shards, "--kv-block-size", "16"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--kv-block-size" in err and "--kv-blocks" in err
    rc = cli.main(["serve", shards, "--kv-blocks", "40"])
    assert rc == 2


def test_serve_paged_cli(shards, capsys, monkeypatch):
    """--kv-block-size/--kv-blocks drive the paged-KV serve daemon end to
    end from the CLI, with output identical to the dense daemon on the
    same stdin prompts."""
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )

    def run(extra):
        monkeypatch.setattr(
            "sys.stdin", io.StringIO("hi paged\nsecond prompt\n")
        )
        rc = cli.main(
            [
                "serve", shards, "--max-new", "4", "--stages", "4",
                "--capacity", "64", "--dtype", "f32", *extra,
            ]
        )
        assert rc == 0
        captured = capsys.readouterr()
        assert '"requests_completed": 2' in captured.err
        return [l for l in captured.out.splitlines() if l.strip()]

    dense = run([])
    paged = run(["--kv-block-size", "16", "--kv-blocks", "40"])
    assert paged == dense and len(paged) == 2
    # automatic prefix caching rides the same daemon, output unchanged
    # (the second prompt shares no prefix — pure cold-path parity here)
    radix = run([
        "--kv-block-size", "16", "--kv-blocks", "40",
        "--prefix-cache", "hbm",
    ])
    assert radix == dense
    # the quantized arena serves from the CLI too (int8 is drift-tolerant
    # by contract, so only completion shape is asserted — token parity
    # belongs to tests/test_kv_quant.py's harness)
    q8 = run([
        "--kv-block-size", "16", "--kv-blocks", "40",
        "--kv-dtype", "int8",
    ])
    assert len(q8) == 2


def test_serve_kv_dtype_flag_fast_fails(shards, capsys):
    """--kv-dtype int8 without the paged KV flags fails in milliseconds,
    before model load (same pre-load pattern as the kv flag pairing)."""
    rc = cli.main(["serve", shards, "--kv-dtype", "int8"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--kv-dtype" in err and "--kv-block-size" in err


def test_serve_prefix_cache_flag_fast_fails(shards, capsys):
    """--prefix-cache without paged KV flags, and --host-pool-blocks
    without --prefix-cache host, fail in milliseconds — before model
    load (same pre-load pattern as the kv flag pairing)."""
    rc = cli.main(["serve", shards, "--prefix-cache", "hbm"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--prefix-cache" in err and "--kv-block-size" in err
    rc = cli.main([
        "serve", shards, "--kv-block-size", "16", "--kv-blocks", "40",
        "--prefix-cache", "hbm", "--host-pool-blocks", "8",
    ])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--host-pool-blocks" in err and "host" in err


def test_serve_disagg_flags_fast_fail(shards, capsys, tmp_path):
    """--disagg flag combinations fail in milliseconds, before model load
    (same pre-load pattern as the kv flag pairing): missing dp, missing
    paged/prefix-cache prerequisites, role flags without --disagg, a bad
    --roles list, and a malformed --profile-json."""
    rc = cli.main(["serve", shards, "--disagg"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--disagg" in err and "--data-parallel" in err
    rc = cli.main([
        "serve", shards, "--disagg", "--data-parallel", "2",
    ])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--kv-block-size" in err
    rc = cli.main([
        "serve", shards, "--disagg", "--data-parallel", "2",
        "--kv-block-size", "16", "--kv-blocks", "40",
    ])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--prefix-cache" in err
    rc = cli.main(["serve", shards, "--prefill-replicas", "1"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--disagg" in err
    rc = cli.main([
        "serve", shards, "--disagg", "--data-parallel", "2",
        "--kv-block-size", "16", "--kv-blocks", "40",
        "--prefix-cache", "hbm", "--prefill-replicas", "2",
    ])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--prefill-replicas" in err and "[1, 1]" in err
    rc = cli.main([
        "serve", shards, "--disagg", "--data-parallel", "2",
        "--kv-block-size", "16", "--kv-blocks", "40",
        "--prefix-cache", "hbm", "--roles", "prefill,bogus",
    ])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--roles" in err
    bad = tmp_path / "profile.json"
    bad.write_text("{}")
    rc = cli.main([
        "serve", shards, "--disagg", "--data-parallel", "2",
        "--kv-block-size", "16", "--kv-blocks", "40",
        "--prefix-cache", "hbm", "--profile-json", str(bad),
    ])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--profile-json" in err


def test_serve_speculate_cli(shards, capsys, monkeypatch):
    """--speculate K drives the speculative serve loop end to end from the
    CLI (stdin prompt → streamed completion), and the banner still prints."""
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    monkeypatch.setattr("sys.stdin", io.StringIO("hello spec world\n"))
    rc = cli.main(
        [
            "serve", shards, "--max-new", "6", "--stages", "4",
            "--capacity", "64", "--dtype", "f32", "--speculate", "2",
        ]
    )
    assert rc == 0
    captured = capsys.readouterr()
    assert '"requests_completed": 1' in captured.err
    assert len(captured.out.strip()) > 0


def test_profile_command_artifacts(tmp_path, capsys):
    out_dir = str(tmp_path / "prof")
    rc = cli.main(
        [
            "profile", "--preset", "tiny_llama", "--out", out_dir,
            "--dtype", "f32", "--decode-tokens", "8", "--hops", "4",
            "--suggest-stages", "4",
        ]
    )
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["prefill"]["capability_c_k"] > 0
    assert payload["decode"]["capability_c_k"] > 0
    assert payload["hop_latency"]["p50_us"] > 0
    assert len(payload["suggested_placement"]) == 4
    assert os.path.exists(os.path.join(out_dir, "profile.json"))
    assert os.path.exists(os.path.join(out_dir, "prefill_fit.png"))
    assert os.path.exists(os.path.join(out_dir, "decode_fit.png"))


def test_profile_command_gpt2_preset(tmp_path, capsys):
    """cmd_profile dispatches init on model_type — gpt2 presets work too
    (ADVICE r2 low: the --preset path was llama-only)."""
    out_dir = str(tmp_path / "prof_gpt2")
    rc = cli.main(
        [
            "profile", "--preset", "tiny_gpt2", "--out", out_dir,
            "--dtype", "f32", "--decode-tokens", "4",
        ]
    )
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["prefill"]["capability_c_k"] > 0
    assert payload["config"]["model_type"] == "gpt2"


def test_profile_hbm_gib_flag(tmp_path, capsys):
    """Explicit --hbm-gib drives max_layers_fit deterministically."""
    out_dir = str(tmp_path / "prof_hbm")
    rc = cli.main(
        [
            "profile", "--preset", "tiny_llama", "--out", out_dir,
            "--dtype", "f32", "--decode-tokens", "4", "--hbm-gib", "16",
        ]
    )
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    # tiny_llama trivially fits 16 GiB: every layer fits
    assert payload["max_layers_fit"] == payload["config"]["num_hidden_layers"]


def test_sharded_head_stage_mismatch_raises():
    """A head pre-stacked for S stages must not silently mis-slice on a mesh
    with a different pipe size (ADVICE r2 medium)."""
    from llm_sharding_tpu.parallel.head import shard_head_host
    from llm_sharding_tpu.parallel.pipeline import ensure_sharded_head

    params = llama.init_params(CFG, jax.random.key(1), dtype=jnp.float32)
    head_host = {k: np.asarray(v) for k, v in params.items() if k != "layers"}
    sharded4 = shard_head_host(CFG, head_host, 4)
    with pytest.raises(ValueError, match="4 stages"):
        ensure_sharded_head(CFG, sharded4, 2)


def test_shared_server_rejects_overlong_prompt(shards, monkeypatch):
    """Prompts beyond the largest admit bucket get a real error, not a bare
    StopIteration (ADVICE r2 low)."""
    from llm_sharding_tpu.runtime.engine import PipelineEngine

    eng = PipelineEngine.from_shards(shards, num_stages=4, dtype=jnp.float32)
    # the bucket ladder tops at 32768 (long-context prompts stream too —
    # r3 weak #6); beyond it is a real error, not a bare StopIteration
    with pytest.raises(ValueError, match="admission bucket"):
        eng._shared_server(40000, 16)


def test_convert_requires_weights(tmp_path):
    src = tmp_path / "empty_model"
    src.mkdir()
    (src / "config.json").write_text(
        json.dumps({"model_type": "gpt2", "n_layer": 1})
    )
    with pytest.raises(FileNotFoundError):
        cli.main(["convert", str(src), str(tmp_path / "out")])


def test_serve_placement_control_line(shards, capsys, monkeypatch):
    """r2 next-#9: the daemon hot-repartitions on a ``:placement`` control
    line (≙ the reference's mid-service config push, ``node_worker.py:
    445-474``). The same prompt before and after the swap must stream the
    same completion — placement is an execution detail — and session
    counters survive the swap."""
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    monkeypatch.setattr(
        "sys.stdin",
        io.StringIO("same prompt\n:placement 0:3,3:4,4:8\nsame prompt\n"),
    )
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "4",
            "--capacity", "64", "--dtype", "f32",
        ]
    )
    assert rc == 0
    captured = capsys.readouterr()
    lines = [l for l in captured.out.splitlines() if l.strip()]
    assert len(lines) == 2
    assert lines[0] == lines[1], "repartition changed the served output"
    assert "placement applied: [(0, 3), (3, 4), (4, 8)]" in captured.err
    assert '"requests_completed": 2' in captured.err


def test_serve_control_line_errors(shards, capsys, monkeypatch):
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    monkeypatch.setattr(
        "sys.stdin",
        # bad ranges; more stages than devices (16 > 8); unknown command —
        # the daemon must survive all three and still serve the final prompt
        io.StringIO(":placement 0:3\n:placement 16\n:bogus\n:counters\nstill up\n"),
    )
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "4",
            "--capacity", "64", "--dtype", "f32",
        ]
    )
    assert rc == 0
    captured = capsys.readouterr()
    assert captured.err.count("bad placement") == 2
    assert "unknown control line" in captured.err
    assert '"requests_submitted": 0' in captured.err
    assert len([l for l in captured.out.splitlines() if l.strip()]) == 1
    assert '"requests_completed": 1' in captured.err


def test_serve_placement_rollback_on_rebuild_failure(shards, capsys, monkeypatch):
    """If the new placement's server fails to build, the daemon rolls the
    placement back and rebuilds on it (the old server object reads the
    engine's arrays live, so keeping it after a swap would mix meshes)."""
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    orig = engine_mod.PipelineEngine.serve
    calls = {"n": 0}

    def flaky(self, **kw):
        calls["n"] += 1
        if calls["n"] == 2:  # 1st: daemon startup; 2nd: rebuild after swap
            raise RuntimeError("synthetic allocation failure")
        return orig(self, **kw)

    monkeypatch.setattr(engine_mod.PipelineEngine, "serve", flaky)
    monkeypatch.setattr(
        "sys.stdin",
        io.StringIO("same prompt\n:placement 2\nsame prompt\n"),
    )
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "4",
            "--capacity", "64", "--dtype", "f32",
        ]
    )
    assert rc == 0
    captured = capsys.readouterr()
    assert "rolled back to [(0, 2), (2, 4), (4, 6), (6, 8)]" in captured.err
    lines = [l for l in captured.out.splitlines() if l.strip()]
    assert len(lines) == 2 and lines[0] == lines[1]
    assert '"requests_completed": 2' in captured.err


def test_launch_two_process_simulation(tmp_path, capsys):
    """``launch`` spawns N jax.distributed workers on this host (≙ the
    reference's run_this.sh:8-17 spawning per-node daemons with per-node
    logs) and worker 0 prints the completion."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    store = str(tmp_path / "store")
    params = llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    shard_store.save_shards(CFG, params, store)
    vocab = {c: i + 3 for i, c in enumerate("abcdefghijklmnopqrstuvwxyz ")}
    vocab.update({"[UNK]": 0, "[BOS]": 1, "[EOS]": 2})
    t = Tokenizer(models.WordLevel(vocab, unk_token="[UNK]"))
    t.pre_tokenizer = pre_tokenizers.Split("", "isolated")
    t.save(os.path.join(store, "tokenizer.json"))
    with open(os.path.join(store, "tokenizer_config.json"), "w") as f:
        json.dump(
            {"tokenizer_class": "PreTrainedTokenizerFast", "unk_token": "[UNK]"},
            f,
        )

    log_dir = str(tmp_path / "logs")
    rc = cli.main(
        [
            "launch", store, "--processes", "2", "--local-devices", "2",
            "--prompt", "hello", "--max-new", "4", "--dtype", "f32",
            "--log-dir", log_dir,
        ]
    )
    assert rc == 0
    assert capsys.readouterr().out.strip(), "worker 0 printed no completion"
    assert os.path.exists(os.path.join(log_dir, "worker_0.log"))
    assert os.path.exists(os.path.join(log_dir, "worker_1.log"))
    with open(os.path.join(log_dir, "worker_1.log")) as f:
        assert "2 processes, 4 global devices" in f.read()


def test_compile_cache_contract(tmp_path, monkeypatch):
    """Where compiled programs are kept: placed from outside → the program
    sets no directory; unset on the CPU → no cache of its own; unset on a
    TPU → one constant directory inside the checkout, and an unusable one is
    an error, not a silent cold start. Wherever a cache is on, its key holds
    the programs' metadata: a cached executable keeps the scope names it was
    compiled with, and a trace is read by them."""
    from llm_sharding_tpu.utils import compile_cache as cc

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.append((k, v))
    )
    in_key = ("jax_compilation_cache_include_metadata_in_key", True)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    for platform in ("tpu", "cpu"):
        assert cc.enable_persistent_cache(platform) == str(tmp_path / "placed")
    assert updates == [in_key, in_key]
    assert not os.path.exists(tmp_path / "placed")
    del updates[:]

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.enable_persistent_cache("cpu") is None and updates == []

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", str(tmp_path / "in_checkout"))
    assert cc.enable_persistent_cache("tpu") == str(tmp_path / "in_checkout")
    assert updates == [
        in_key, ("jax_compilation_cache_dir", str(tmp_path / "in_checkout"))
    ]
    (tmp_path / "a_file").write_text("")
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", str(tmp_path / "a_file" / "x"))
    with pytest.raises(OSError):
        cc.enable_persistent_cache("tpu")


@pytest.mark.parametrize("argv", [
    ["launch", "store", "--prompt", "hi"],
    ["convert", "model_dir", "out_dir"],
])
def test_spawning_and_offline_commands_never_take_the_chip(argv, monkeypatch):
    """A chip belongs to one process: ``launch`` (a parent of workers) and
    ``convert`` (an offline file transform) must reach their command
    without ``main`` initialising a backend."""
    def boom(*a, **k):
        raise AssertionError("cli.main initialised a backend")

    monkeypatch.setattr(jax, "devices", boom)
    seen = []
    monkeypatch.setattr(
        cli, "cmd_" + argv[0], lambda args: seen.append(args.command) or 0
    )
    assert cli.main(argv) == 0 and seen == [argv[0]]


def test_launch_is_a_cpu_simulation_only(capsys):
    with pytest.raises(SystemExit):
        cli.main(["launch", "store", "--prompt", "hi", "--platform", "inherit"])
    assert "--platform" in capsys.readouterr().err


def test_serve_command_stop_flag(shards, capsys, monkeypatch):
    """--stop plumbs through to submit(): the daemon serves with a stop
    string configured (the string check itself is pinned in
    tests/test_serve.py::test_stop_sequences_truncate_and_free)."""
    from llm_sharding_tpu.runtime import engine as engine_mod

    tok = IdTokenizer()
    monkeypatch.setattr(
        engine_mod.PipelineEngine, "_require_tokenizer", lambda self: tok
    )
    orig = engine_mod.PipelineEngine.from_shards.__func__

    def patched(cls, *a, **k):
        eng = orig(cls, *a, **k)
        eng.tokenizer = tok  # server-side stop check reads engine.tokenizer
        return eng

    monkeypatch.setattr(
        engine_mod.PipelineEngine, "from_shards", classmethod(patched)
    )
    monkeypatch.setattr("sys.stdin", io.StringIO("hi\n"))
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "4",
            "--capacity", "64", "--dtype", "f32", "--stop", "0",
        ]
    )
    assert rc == 0
    assert '"requests_completed": 1' in capsys.readouterr().err


def test_serve_command_data_parallel(shards, capsys, monkeypatch):
    """dp daemon: two replica servers over device groups, prompts served."""
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    monkeypatch.setattr("sys.stdin", io.StringIO("hi there\nsecond one\n"))
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "2",
            "--data-parallel", "2", "--capacity", "64", "--dtype", "f32",
        ]
    )
    assert rc == 0
    captured = capsys.readouterr()
    assert len([l for l in captured.out.splitlines() if l.strip()]) == 2
    assert '"requests_completed": 2' in captured.err
    assert "2 replicas" in captured.err


def test_serve_command_dp_drain_spawn(shards, capsys, monkeypatch):
    """dp daemon elasticity control lines: ':drain N' migrates replica N's
    work and closes it (refusing an unknown group typed), ':spawn' brings
    a replica back on the freed group — prompts keep serving throughout."""
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    monkeypatch.setattr(
        "sys.stdin",
        io.StringIO(
            "hi there\n:drain 1\nsecond one\n:spawn\nthird line\n"
            ":drain 9\n:bogus\n"
        ),
    )
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "2",
            "--data-parallel", "2", "--min-replicas", "1",
            "--capacity", "64", "--dtype", "f32",
        ]
    )
    assert rc == 0
    captured = capsys.readouterr()
    assert len([l for l in captured.out.splitlines() if l.strip()]) == 3
    err = captured.err
    assert "replica 1 drained" in err
    assert "replica spawned on group 1" in err
    assert "drain failed: no live replica 9" in err
    assert "unknown control line ':bogus'" in err
    assert '"requests_completed": 3' in err


def test_serve_command_disagg_daemon(shards, capsys, monkeypatch):
    """--disagg daemon end to end from the CLI: prompts prefill on the
    prefill replica, hand off, and stream back — banner names the roles."""
    from llm_sharding_tpu.obs.metrics import DISAGG_HANDOFFS
    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )
    monkeypatch.setattr("sys.stdin", io.StringIO("hi there\nsecond one\n"))
    moved0 = (
        DISAGG_HANDOFFS.labels(outcome="ok").value
        + DISAGG_HANDOFFS.labels(outcome="cold").value
    )
    rc = cli.main(
        [
            "serve", shards, "--max-new", "4", "--stages", "2",
            "--data-parallel", "2", "--capacity", "64", "--dtype", "f32",
            "--disagg", "--kv-block-size", "8", "--kv-blocks", "40",
            "--prefix-cache", "hbm",
        ]
    )
    assert rc == 0
    captured = capsys.readouterr()
    assert len([l for l in captured.out.splitlines() if l.strip()]) == 2
    assert "disagg roles: prefill,decode" in captured.err
    assert '"requests_completed": 2' in captured.err
    moved = (
        DISAGG_HANDOFFS.labels(outcome="ok").value
        + DISAGG_HANDOFFS.labels(outcome="cold").value
    ) - moved0
    assert moved == 2


# ------------------------------------------------- production ingress flags


def test_serve_ingress_flag_validation_fast_fails(shards, tmp_path, capsys):
    """ISSUE 9: flag mismatches and a malformed tenants file fail in
    milliseconds — before any model load."""
    rc = cli.main(
        ["serve", shards, "--tenants-config", "whatever.json"]
    )
    assert rc == 2
    assert "--tenants-config needs --http-port" in capsys.readouterr().err

    rc = cli.main(["serve", shards, "--autoscale"])
    assert rc == 2
    assert "--autoscale needs --data-parallel" in capsys.readouterr().err

    bad = tmp_path / "bad_tenants.json"
    bad.write_text('{"tenants": {"a": {"weight": 0}}}')
    rc = cli.main(
        ["serve", shards, "--http-port", "1", "--tenants-config", str(bad)]
    )
    assert rc == 2
    assert "bad --tenants-config" in capsys.readouterr().err


def test_serve_command_http_ingress(shards, capsys, monkeypatch):
    """serve --http-port: the daemon answers OpenAI-style completions over
    HTTP (token ids in, token ids out) while the stdin loop idles; tenant
    policy comes from --tenants-config."""
    import http.client
    import threading as _th

    from llm_sharding_tpu.runtime import engine as engine_mod

    monkeypatch.setattr(
        engine_mod.PipelineEngine,
        "_require_tokenizer",
        lambda self: IdTokenizer(),
    )

    # feed stdin from a pipe we keep open until the HTTP round trip lands
    r_fd, w_fd = os.pipe()
    monkeypatch.setattr("sys.stdin", os.fdopen(r_fd, "r"))
    result = {}

    def drive():
        # wait for the banner's port line on our side is impossible from a
        # thread (stderr is captured) — poll the known loopback port range
        # by asking the ingress object via the module singleton instead:
        # simplest is to retry the fixed port below until it answers.
        deadline = 60.0
        import time as _time

        t0 = _time.monotonic()
        while _time.monotonic() - t0 < deadline:
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", 18431, timeout=5
                )
                conn.request(
                    "POST", "/v1/completions",
                    json.dumps({"prompt": [1, 2, 3], "max_tokens": 4}),
                    {
                        "Content-Type": "application/json",
                        "X-Tenant": "default",
                    },
                )
                resp = conn.getresponse()
                result["status"] = resp.status
                result["body"] = json.loads(resp.read())
                conn.close()
                break
            except OSError:
                _time.sleep(0.1)
        os.close(w_fd)  # EOF -> the daemon exits its stdin loop

    t = _th.Thread(target=drive)
    t.start()
    rc = cli.main(
        [
            "serve", shards, "--max-new", "8", "--stages", "2",
            "--capacity", "64", "--dtype", "f32",
            "--http-port", "18431",
        ]
    )
    t.join(timeout=120)
    assert rc == 0
    assert result.get("status") == 200, result
    assert len(result["body"]["choices"][0]["token_ids"]) == 4
    err = capsys.readouterr().err
    assert "ingress: http://127.0.0.1:18431/v1/completions" in err


def test_stdin_lines_burst_in_one_write(monkeypatch):
    """The select-driven stdin reader must deliver EVERY line of a burst
    written in one chunk — mixing select() with buffered readline()
    stranded the second line in Python's read-ahead buffer (a
    `printf ':drain 1\\n:spawn\\n' > fifo` burst lost its second control
    line)."""
    import threading as _th

    r_fd, w_fd = os.pipe()
    monkeypatch.setattr("sys.stdin", os.fdopen(r_fd, "r"))
    os.write(w_fd, b"one\ntwo\nthree")  # two full lines + an EOF tail
    os.close(w_fd)
    lines = list(cli._stdin_lines(_th.Event()))
    assert lines == ["one\n", "two\n", "three"]


def test_serve_sigterm_graceful_drain(shards):
    """ISSUE 9 satellite: SIGTERM means drain, not die — the daemon flips
    DRAINING, finishes in-flight work, and exits 0 (k8s rolling restarts
    stop killing live streams). Driven through a real subprocess signal."""
    import signal as _signal
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    proc = subprocess.Popen(
        [
            _sys.executable, "-m", "llm_sharding_tpu", "serve", shards,
            "--stages", "2", "--capacity", "64", "--dtype", "f32",
            "--max-new", "4",
        ],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, env=env, text=True,
    )
    try:
        # wait for the daemon banner (model built, loop entered)
        for line in proc.stderr:
            if "serving" in line:
                break
        else:
            pytest.fail(
                f"daemon never came up (rc={proc.poll()})"
            )
        proc.send_signal(_signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err
        assert "SIGTERM: draining" in err
        assert "drained; exiting 0" in err
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()


def test_a_cached_program_never_comes_back_with_a_stale_scope(tmp_path):
    """Why the cache key holds metadata (``utils/compile_cache.py``): two
    processes share one cache directory and compile the SAME computation
    under a different ``jax.named_scope``. Loaded from the cache, the second
    must still carry its own scope — with JAX's default key it is served the
    first one's executable, first one's names and all, and every metric a
    profiler trace reads by scope goes quietly wrong."""
    import subprocess
    import sys

    code = (
        "import re, sys, jax, jax.numpy as jnp\n"
        "from llm_sharding_tpu.utils.compile_cache import "
        "enable_persistent_cache\n"
        "assert enable_persistent_cache('cpu')\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "def probe(x):\n"
        "    with jax.named_scope(sys.argv[1]):\n"
        "        return jnp.tanh(x @ x)\n"
        "text = jax.jit(probe).lower(jnp.ones((64, 64))).compile().as_text()\n"
        "print(sorted(set(re.findall(r'/(kv_\\w+)/dot_general', text))))\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    seen = []
    for scope in ("kv_take", "kv_put"):
        out = subprocess.run(
            [sys.executable, "-c", code, scope], env=env, cwd=repo,
            capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        seen.append(out.stdout.strip().splitlines()[-1])
    assert seen == ["['kv_take']", "['kv_put']"]
    assert os.listdir(tmp_path / "cache"), "the cache was never written"
