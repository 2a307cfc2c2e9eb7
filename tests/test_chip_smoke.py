"""``chip_smoke.py`` on the CPU mesh: its store writer, HTTP driver and
assertions at a tiny config with the kernels in interpret mode — and its own
``main`` refusing to pass without a TPU.

The smoke proper runs on the chip (see the script's docstring); these tests
keep its pieces from rotting between chip runs.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

#: the FULL spec's shape at toy size: block 8, chunk 16, a long prompt four
#: chunks long and a radix-hit suffix that still needs chunking. The EOS id
#: sits outside the vocabulary so random weights cannot end a request early.
TINY = {
    "preset": "tiny_llama",
    "overrides": {"num_hidden_layers": 2, "eos_token_id": 10**6,
                  "eos_token_ids": [10**6]},
    "seed": 7,
    "dtype": "f32",
    "quantize": True,
    "serve": {"capacity": 128, "batch_per_slot": 4, "kv_block_size": 8,
              "kv_blocks": 65, "prefill_chunk": 16},
    "max_tokens": 6,
    "short_prompt": 6,
    "long_prompt": 60,
    "shared_prefix": 32,
    "shared_suffix": 24,
}


def test_main_refuses_without_a_tpu(tmp_path):
    """Here (no TPU) the script exits non-zero, names the missing device and
    prints no result line — from a copy, so its ``.smoke/`` lands in tmp."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert p.returncode != 0
    assert "found no TPU" in p.stderr and "'cpu'" in p.stderr
    assert '"ok"' not in p.stdout


def test_kernel_check_runs_every_variant_in_interpret_mode():
    cfg = chip_smoke.model_config(TINY)
    cases = chip_smoke.kernel_cases(TINY)
    assert {(c["kernel"], c["kv_dtype"]) for c in cases} == {
        (k, d) for k in ("paged_decode", "paged_prefill")
        for d in ("bf16", "int8", "fp8")
    } | {("flash", "bf16")}
    # the decode kernel in each state of its walk, over every arena
    assert {(c["kv_dtype"], c["state"]) for c in cases
            if c["kernel"] == "paged_decode"} == {
        (d, s) for d in ("bf16", "int8", "fp8")
        for s in chip_smoke.DECODE_STATES
    }
    for case in cases:
        assert case["table_width"] == 16 and case["block_size"] == 8
        err = chip_smoke.check_kernel(cfg, case, "interpret")
        assert err <= chip_smoke.KERNEL_TOL, (case, err)
    # one live row at an eighth of the table beside dead rows; every row at
    # the full table
    _, _, nlive = chip_smoke.kernel_inputs(cfg, next(
        c for c in cases if c["state"] == "one_row_eighth"))
    assert list(nlive) == [2, 0, 0, 0]
    _, _, nlive = chip_smoke.kernel_inputs(cfg, next(
        c for c in cases if c["state"] == "all_rows_full"))
    assert list(nlive) == [16] * 4


def test_decode_timing_runs_in_interpret_mode():
    """The chip check's timing of the decode kernel against the XLA path
    (``time_decode``), at toy size: both sides run and report a time."""
    cfg = chip_smoke.model_config(TINY)
    case = next(c for c in chip_smoke.kernel_cases(TINY)
                if c["state"] == "one_row_eighth" and c["kv_dtype"] == "bf16")
    times = chip_smoke.time_decode(cfg, case, "interpret", calls=2)
    assert set(times) == {"kernel_ms", "xla_ms"}
    assert all(t > 0 for t in times.values())


@pytest.mark.parametrize("rows", [4, 160])
def test_expert_kernel_check_runs_in_interpret_mode(rows):
    """``chip_smoke.py --moe``'s check at toy widths: the expert kernel
    emulated against its XLA path, decode rows and grouped prefill rows."""
    from llm_sharding_tpu.models.config import tiny_olmoe

    err = chip_smoke.check_moe_kernel(tiny_olmoe(), rows, "interpret")
    assert err <= chip_smoke.KERNEL_TOL, (rows, err)


@pytest.mark.parametrize("held", [None, (0, 8)])
@pytest.mark.parametrize("met", chip_smoke.MOE_MET)
def test_expert_call_timing_meets_the_experts_it_says(met, held):
    """``chip_smoke.py --moe``'s timing of a decode call at toy widths: one
    live row meets 0, 1 and 8 experts, all held here or a share of them (the
    call itself checks what it read); a CPU time is no speed."""
    shape = {"name": "toy", "H": 64, "F": 128, "E": 16, "held": held}
    assert chip_smoke.time_moe(shape, met, "interpret", calls=2) > 0


@pytest.mark.parametrize("held", [None, (0, 8)])
def test_expert_call_parts_are_timed_each_alone(held):
    """``chip_smoke.py --moe``'s split of a decode call at toy widths (PR
    63): the loop around nothing, what ``expert_mlp`` computes before its
    kernel, the kernel on counts built outside the loop, and the two XLA
    parts the kernel took in (the scale slices, the combine) — a CPU time is
    no speed; and Keye's shape is timed beside OLMoE's and GigaChat's."""
    shape = {"name": "toy", "H": 64, "F": 128, "E": 16, "held": held}
    parts = chip_smoke.time_moe_parts(shape, "interpret", calls=2)
    assert list(parts) == ["loop", "tiles", "kernel", "scales", "combine"]
    assert all(us > 0 for us in parts.values())
    keye = [s for s in chip_smoke.MOE_TIMED if s["name"] == "keye_vl2_30b_a3b"]
    assert keye == [{"name": "keye_vl2_30b_a3b", "H": 2048, "F": 768,
                     "E": 128, "held": None}]


#: ``--kv-write``'s shapes at toy widths: key and value alike, a latent
#: arena, keys wider than values over a pool too small to give every row
#: blocks of its own (MiMo's window pool)
KV_TOY = {
    "alike": {"name": "toy", "heads": 2, "dk": 16, "dv": 16, "blocks": 40,
              "layers": 3},
    "latent": {"name": "toy_latent", "heads": 1, "dk": 24, "dv": 0,
               "blocks": 40, "layers": 2},
    "small_pool": {"name": "toy_swa", "heads": 2, "dk": 16, "dv": 8,
                   "blocks": 5, "layers": 2},
}


@pytest.mark.parametrize("form", chip_smoke.KV_WRITE_FORMS)
@pytest.mark.parametrize("shape", sorted(KV_TOY))
def test_kv_write_forms_agree_and_are_timed(shape, form, monkeypatch):
    """``chip_smoke.py --kv-write`` at toy widths: each form of a chunk's
    K/V write (the tiles the program writes, the rows they replaced, the
    loop of slices and the copy kernel timed beside them) leaves the arenas
    as the row-wise write does outside block 0, and its timing loop runs (a
    CPU time is no speed)."""
    monkeypatch.setattr(
        chip_smoke, "KV_CHUNK", {"rows": 2, "chunk": 32, "block_size": 8})
    assert chip_smoke.check_kv_write(KV_TOY[shape], form, interpret=True)
    assert chip_smoke.time_kv_write(
        KV_TOY[shape], form, calls=2, interpret=True) > 0


def test_kv_write_shapes_are_the_cells(monkeypatch):
    """The shapes ``--kv-write`` times are those of the benchmark's five
    configurations (MiMo's two kinds of layer each), at their pools."""
    import json
    import os

    cfgs = os.path.join(chip_smoke.HERE, "benchmark", "configs")
    got = {s["name"]: s for s in chip_smoke.KV_WRITE_SHAPES}
    for name in ("olmoe_1b_7b", "qwen25_7b", "qwen25_14b_pp4",
                 "gigachat31_702b_a36b"):
        with open(os.path.join(cfgs, name + ".json")) as f:
            serve = json.load(f)["serve"]
        assert got[name]["blocks"] == serve["kv_blocks"], name
        assert (serve["batch_per_slot"], serve["prefill_chunk"],
                serve["kv_block_size"]) == tuple(chip_smoke.KV_CHUNK.values())
    assert {"mimo_v25.swa", "mimo_v25.full"} <= set(got)


#: ``--kv-decode``'s shapes at toy widths: a fold of query heads with keys
#: and values alike, and a window layer with a sink, keys wider than values
#: and the blocks behind the window freed
KV_DECODE_TOY = {
    "fold": {"name": "toy", "heads": 2, "group": 3, "dk": 16, "dv": 16,
             "blocks": 40, "layers": 3, "table": 8, "context": 21},
    "window": {"name": "toy_swa", "heads": 2, "group": 2, "dk": 16, "dv": 8,
               "blocks": 13, "layers": 2, "table": 8, "context": 40,
               "window": 12},
    # Keye's: one row of a long context, a walk of three cells of 8 blocks
    "long": {"name": "toy_long", "heads": 4, "group": 2, "dk": 16, "dv": 16,
             "blocks": 81, "layers": 2, "table": 24, "context": 150},
}


@pytest.mark.parametrize("live", chip_smoke.KV_DECODE_LIVE)
@pytest.mark.parametrize("shape", sorted(KV_DECODE_TOY))
def test_kv_decode_forms_agree_and_are_traced(
    shape, live, monkeypatch, tmp_path
):
    """``chip_smoke.py --kv-decode`` at toy widths: the fused decode call
    leaves the arenas as the scatter pair does outside block 0 and returns
    the same output (the same kernel over the same bytes), one and four
    live rows of the slot's four; and its traced timing runs end to end — a
    CPU trace holds no TPU plane, so it reads nothing, and no speed."""
    monkeypatch.setattr(
        chip_smoke, "KV_CHUNK", {"rows": 4, "chunk": 32, "block_size": 8})
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    got = chip_smoke.check_kv_decode(KV_DECODE_TOY[shape], live, "interpret")
    assert got == {"arenas_same": True, "max_err": 0.0}
    if live == 1:
        timed = chip_smoke.time_kv_decode(
            KV_DECODE_TOY[shape], live, "fused", backend="interpret", runs=1)
        assert timed == {"us_per_layer_call": 0.0, "ops_us": []}


def test_kv_decode_shapes_are_the_cells():
    """The shapes ``--kv-decode`` times are OLMoE's, the 7B's and MiMo's
    window layers' as ``--kv-write`` holds them and Keye's as its
    configuration does, a table as wide as the cell's capacity; Keye's three
    contexts lie inside a reply of its cell (2,048 to 8,704 tokens) and its
    slope is read between the first and the last."""
    import json
    import os

    cfgs = os.path.join(chip_smoke.HERE, "benchmark", "configs")
    write = {s["name"]: s for s in chip_smoke.KV_WRITE_SHAPES}
    for shape in chip_smoke.KV_DECODE_SHAPES:
        with open(os.path.join(
                cfgs, shape["name"].split(".")[0] + ".json")) as f:
            cfg = json.load(f)
        serve = cfg["serve"]
        if shape["name"] in write:
            for key in ("heads", "dk", "dv", "blocks", "layers"):
                assert shape[key] == write[shape["name"]][key], (shape, key)
        else:
            assert (shape["heads"], shape["heads"] * shape["group"],
                    shape["dk"], shape["dv"], shape["blocks"],
                    shape["layers"]) == (
                cfg["num_key_value_heads"], cfg["num_attention_heads"],
                cfg["head_dim"], cfg["head_dim"], serve["kv_blocks"],
                cfg["num_hidden_layers"])
        assert shape["table"] == serve["capacity"] // serve["kv_block_size"]
        assert max(shape["contexts"]) < serve["capacity"]
        assert shape.get("window", 0) == (
            cfg.get("sliding_window") or 0 if "." in shape["name"] else 0)
    keye = chip_smoke.KV_DECODE_SHAPES[-1]
    assert keye["name"] == "keye_vl2_30b_a3b"
    assert keye["contexts"] == (2560, 5120, 8704)
    # the parent's readings (PERF.md, PR 50): 6.0 ns a token of context
    assert chip_smoke.walk_slope([(2560, 22.2), (5120, 37.4), (8704, 59.4)],
                                 1) == 6.05
    assert chip_smoke.walk_slope([(2560, 40.0), (8704, 138.3)], 4) == 4.0


#: ``--index-scores``' shape at toy widths: two live rows of four, a table of
#: 24 blocks of 8 tokens, four index heads
INDEX_TOY = {"name": "toy", "rows": 4, "live": 2, "heads": 4, "lanes": 128,
             "block_size": 8, "table": 24, "blocks": 97, "layers": 2}


@pytest.mark.parametrize("width", [None, 1, 8, 16])
def test_index_scores_probe_at_toy_widths(width, monkeypatch, tmp_path):
    """``chip_smoke.py --index-scores`` at toy widths, the kernel in
    interpret mode: at the kernel's own cell width, at one and eight blocks a
    cell and at one that does not divide the table (16: the table padded to
    32) the scores are the XLA branch's on every attendable column, nothing
    else is attendable (block 0 holds ``inf``), the hash is the same at every
    width, and the traced timing runs end to end — a CPU trace holds no TPU
    plane, so it reads nothing, and no speed."""
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    want = {}
    got = chip_smoke.time_index_scores(
        INDEX_TOY, 150, width, want, runs=1, interpret=True)
    own = chip_smoke.time_index_scores(
        INDEX_TOY, 150, None, want, runs=1, interpret=True)
    assert sorted(want) == sorted({(150, 24), (150, 32 if width == 16 else 24)})
    assert got["masked_same"] and got["max_err"] <= 1e-3
    assert got["hash"] == own["hash"]
    assert (got["kernel_us"], got["ops_us"]) == (0.0, [])


def test_index_shape_is_the_cell():
    """The shape ``--index-scores`` times is Keye's as its configuration
    holds it — the indexer's heads, its stored key's lanes, the cell's table,
    pool and depth, the slot's four rows of which one decodes — at the
    contexts ``--kv-decode`` walks, and the widths it sweeps include the one
    the kernel's rule takes there."""
    import json
    import os

    from llm_sharding_tpu.ops.paged_attention import index_blocks_per_cell

    shape = chip_smoke.INDEX_SHAPE
    with open(os.path.join(chip_smoke.HERE, "benchmark", "configs",
                           shape["name"] + ".json")) as f:
        cfg = json.load(f)
    serve, sa = cfg["serve"], cfg["sa_config"]
    assert (shape["heads"], shape["rows"], shape["block_size"],
            shape["blocks"], shape["layers"]) == (
        sa["indexer_num_heads"], serve["batch_per_slot"],
        serve["kv_block_size"], serve["kv_blocks"], cfg["num_hidden_layers"])
    assert shape["lanes"] == -(-sa["indexer_head_dim"] // 128) * 128
    assert shape["table"] == serve["capacity"] // serve["kv_block_size"]
    assert shape["live"] == 1  # one closed-loop client
    assert shape["contexts"] == chip_smoke.KV_DECODE_SHAPES[-1]["contexts"]
    assert min(shape["contexts"]) > sa["topk"]  # the selection engages
    assert index_blocks_per_cell(
        shape["table"], shape["block_size"], shape["lanes"], 2
    ) in chip_smoke.INDEX_WIDTHS
    assert chip_smoke.INDEX_WIDTHS[0] is None
    # the parent's readings (PERF.md, PR 56): 2.43 ns a token of context
    assert chip_smoke.walk_slope(
        [(2560, 7.43), (5120, 13.64), (8704, 22.33)], 1) == 2.43


#: ``--select``'s shape at toy widths: a slot of four rows over 256 columns
SELECT_TOY = {"name": "toy", "rows": 4, "width": 256, "topk": 64, "layers": 2,
              "lives": (1, 4), "contexts": (100, 200)}


@pytest.mark.parametrize("form", list(chip_smoke.SELECT_FORMS))
def test_select_probe_at_toy_widths(form, monkeypatch, tmp_path):
    """``chip_smoke.py --select`` at toy widths, the kernel in interpret mode:
    in every form it times — the XLA search, ``select_mask`` as the tree
    resolves it, the kernel at 1, 2 and 4 bits a pass — one live row and four,
    with and without zeros of both signs tying across the ``topk``-th score,
    the mask is ``select_tokens``' set and keeps ``topk`` columns a live row
    and layer; the traced timing runs end to end (a CPU trace holds no TPU
    plane, so it reads nothing, and no speed)."""
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    assert form in chip_smoke.select_forms()
    for live in SELECT_TOY["lives"]:
        for ties in (False, True):
            got = chip_smoke.time_select(
                SELECT_TOY, live, 200, form, ties, runs=1, interpret=True)
            assert got["oracle_set"], (live, ties)
            assert got["kept"] == 2 * live * 64
            assert (got["us_per_layer_call"], got["kernel_us"],
                    got["ops_us"]) == (0.0, 0.0, [])
    # the mask alone (no trace) at a slot of another size, as --select
    # checks 1, 2, 8 and 16 rows on the chip
    assert chip_smoke.SELECT_ROWS_CHECKED == (1, 2, 8, 16)
    got = chip_smoke.time_select(dict(SELECT_TOY, rows=8), 4, 100, form,
                                 ties=True, runs=0, interpret=True)
    assert got["oracle_set"] and got["kept"] == 2 * 4 * 64


def test_select_shape_is_the_cell():
    """The shape ``--select`` times is Keye's as its configuration holds it —
    a slot's rows over the window's columns, the indexer's ``topk``, the
    depth — at the contexts ``--index-scores`` walks (all past ``topk``: the
    selection engages), one live row as the cell has and four; the tying
    input's zeros tie ACROSS the ``topk``-th score; and ``select_mask`` runs
    that shape as the kernel wherever a TPU is the backend."""
    import json
    import os
    from unittest import mock

    import jax
    import numpy as np

    from llm_sharding_tpu.ops import paged_attention as pa

    shape = chip_smoke.SELECT_SHAPE
    with open(os.path.join(chip_smoke.HERE, "benchmark", "configs",
                           shape["name"] + ".json")) as f:
        cfg = json.load(f)
    serve, sa = cfg["serve"], cfg["sa_config"]
    assert (shape["rows"], shape["width"], shape["topk"], shape["layers"]) == (
        serve["batch_per_slot"], serve["capacity"], sa["topk"],
        cfg["num_hidden_layers"])
    assert shape["contexts"] == chip_smoke.INDEX_SHAPE["contexts"]
    assert shape["lives"] == (1, shape["rows"])
    assert chip_smoke.SELECT_FORMS[:2] == ("xla", "own")
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert pa.select_path((shape["rows"],), shape["width"]) == "kernel"
        assert pa.select_path(
            (1, serve["prefill_chunk"]), shape["width"]) == "xla"
    toy = dict(SELECT_TOY, layers=1)
    scores = np.asarray(chip_smoke.select_inputs(toy, 1, 200, ties=True)[
        "scores"])[0, 0]
    assert 0 < (scores > 0).sum() < toy["topk"] < (scores == 0).sum()
    assert (np.signbit(scores) & (scores == 0)).any()
    assert (~np.signbit(scores) & (scores == 0)).any()
    assert np.isneginf(scores[200:]).all()


def test_store_writer_driver_and_assertions_on_cpu(tmp_path, monkeypatch):
    """The smoke's daemon phase end to end at toy size: seeded store through
    the product's writer, the real ``serve`` daemon as a child, the smoke's
    traffic over HTTP, its assertions, SIGTERM → drained."""
    from llm_sharding_tpu.utils import shard_store

    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    # a per-file cap the toy units exceed, as the 7B units exceed the real
    # one: the daemon below serves a store whose units span several files
    monkeypatch.setattr(shard_store, "MAX_FILE_BYTES", 16 << 10)
    store = str(tmp_path / "store")
    first = chip_smoke.write_store(TINY, store)
    assert not first["reused"] and first["bytes"]["blocks"] == 2
    assert first["bytes"]["largest_file"] < (16 << 10) + 4096  # + zip headers
    assert os.path.exists(os.path.join(store, "block_0.part1.npz"))
    assert os.path.exists(os.path.join(store, "embedding.part1.npz"))
    assert chip_smoke.write_store(TINY, store)["reused"]
    # one tensor at a time, each from its own stream
    get = chip_smoke.tensor_source(chip_smoke.model_config(TINY), TINY["seed"])
    np.testing.assert_array_equal(
        get("model.layers.1.mlp.up_proj.weight"),
        get("model.layers.1.mlp.up_proj.weight"),
    )
    with pytest.raises(KeyError):
        get("model.layers.0.self_attn.o_proj.bias")

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PAGED_FORCE_KERNEL="interpret",
        PYTHONPATH=REPO,
    )
    env.pop("XLA_FLAGS", None)  # one device: --stages 1
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # the program's own choice
    with chip_smoke.Daemon(
        store, chip_smoke.serve_args(TINY, 1, 1), env
    ) as d:
        d.wait_ready(300.0)
        dev = d.statz()["device"]
        assert dev["platform"] == "cpu" and dev["compile_cache_dir"] is None
        traffic = chip_smoke.drive(d, TINY, vocab=256)
        assert traffic["sent"] == 10
        assert traffic["prefix_hit_tokens"] >= TINY["shared_prefix"]
        served = chip_smoke.check_served(
            d, traffic["sent"],
            {"platform": "cpu", "attn_backend": "interpret"},
        )
        assert served["blocks_read"]["prefill"] > 0
        # the assertions bite: this daemon did not run on a TPU kernel
        with pytest.raises(SystemExit, match="ran on 'cpu'"):
            chip_smoke.check_served(
                d, traffic["sent"],
                {"platform": "tpu", "attn_backend": "kernel"},
            )
        d.drain()
    assert d.proc.returncode == 0


def test_metric_parser_and_memory_check():
    page = (
        '# HELP x\nserver_attn_backend{backend="kernel"} 2\n'
        'server_attn_backend{backend="xla"} 0\n'
        "server_attn_blocks_read_total 41\n"
        "server_attn_blocks_read_total_created 9\n"
    )
    assert chip_smoke.metric(page, "server_attn_backend", backend="kernel") == 2
    assert chip_smoke.metric(page, "server_attn_blocks_read_total") == 41
    assert chip_smoke.metric(page, "absent") == 0

    gib = 2**30
    sizes = {"block": gib // 4, "blocks": 28, "head": 2 * gib}
    dev = {"memory": [
        {"id": i, "bytes_in_use": 3 * gib, "peak_bytes_in_use": 3 * gib}
        for i in range(4)
    ]}
    # 7 layers (1.75) + head/4 (0.5) + arena/4 (0.25) = 2.5 GiB share
    assert chip_smoke.check_memory(dev, sizes, gib, 4, 1, slack=gib) == [3.0] * 4
    dev["memory"][0]["peak_bytes_in_use"] = 9 * gib  # the old chip-0 detour
    with pytest.raises(SystemExit, match="peaked at 9.00 GiB"):
        chip_smoke.check_memory(dev, sizes, gib, 4, 1, slack=gib)
    with pytest.raises(SystemExit, match="4 devices hold data"):
        chip_smoke.check_memory(dev, sizes, gib, 2, 1, slack=gib)


def test_parent_imports_nothing_that_touches_the_chip():
    """The parent is stdlib only: importing the module (what ``main`` runs
    under before it spawns children) must not pull in jax."""
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke; "
        "chip_smoke.prompts(chip_smoke.FULL, 1000); "
        "print(json.dumps(sorted(m for m in sys.modules "
        "if m.split('.')[0] in ('jax', 'jaxlib', 'numpy', "
        "'llm_sharding_tpu'))))" % REPO
    )
    out = subprocess.run(
        [sys.executable, "-c", "import json; " + code],
        capture_output=True, text=True, check=True,
    ).stdout
    assert json.loads(out) == []


SSM_TOY = {"layers": 2, "rows": 3, "heads": 8, "head_dim": 8, "state": 128,
           "groups": 2}


@pytest.mark.parametrize("live", [0, 1, 3])
def test_ssm_rows_check_and_timing_at_toy_widths(live):
    """``chip_smoke.py --ssm``'s check at toy widths, the kernel emulated: it
    agrees with the XLA loop, a row that is not live comes back bit for bit,
    and the timing loop runs with the state carried (a CPU time is no
    speed)."""
    got = chip_smoke.check_ssm_rows(SSM_TOY, live, backend="interpret")
    assert got["dead_rows_untouched"]
    assert max(got["y_err"], got["s_err"]) <= chip_smoke.SSM_TOL
    assert chip_smoke.time_ssm_rows(SSM_TOY, live, "interpret", calls=2) > 0


def test_ssm_shape_is_the_cells():
    """The shape ``--ssm`` runs is the benchmark configuration's mixer."""
    import json
    import os

    with open(os.path.join(chip_smoke.HERE, "benchmark", "configs",
                           "nemotron3_super_120b_a12b.json")) as f:
        cfg = json.load(f)
    s = chip_smoke.SSM_SHAPE
    assert (s["heads"], s["head_dim"], s["state"], s["groups"]) == (
        cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"],
        cfg["n_groups"])
    assert s["layers"] == cfg["hybrid_override_pattern"][
        :cfg["num_hidden_layers"]].count("M")
    assert s["rows"] == cfg["serve"]["batch_per_slot"]


MIXER_TOY = dict(hidden_size=16, mamba_expand=8, intermediate_size=16,
                 num_attention_heads=1, num_key_value_heads=1, vocab_size=256,
                 mamba_dt_rank=8, mamba_d_state=8)


@pytest.mark.parametrize("live", [0, 1, 4])
def test_mixer_step_check_at_toy_widths(live, monkeypatch):
    """``chip_smoke.py --ssm``'s Mamba-1 check at toy widths, the kernel
    emulated: the fused decode step (the leaves handed whole) agrees with the
    split path (a layer's slices) over every layer of the stack, and a row
    that is not live keeps its state and its conv tail bit for bit."""
    monkeypatch.setattr(chip_smoke, "MIXER_KEYS", MIXER_TOY)
    monkeypatch.setattr(chip_smoke, "MIXER_LAYERS", 3)
    got = chip_smoke.check_mixer_step(live, backend="interpret")
    assert got["dead_rows_untouched"]
    assert max(got["h_err"], got["s_err"], got["c_err"]) <= chip_smoke.MIXER_TOL


def test_mixer_shape_is_the_cells():
    """The mixer ``--ssm`` times is the benchmark configuration's, between
    projections of another hidden size."""
    import json
    import os

    from llm_sharding_tpu.models.config import ModelConfig, jamba2_3b_keys

    with open(os.path.join(chip_smoke.HERE, "benchmark", "configs",
                           "jamba2_3b.json")) as f:
        cfg = json.load(f)
    toy = ModelConfig.from_hf_config(jamba2_3b_keys(**chip_smoke.MIXER_KEYS))
    assert toy.ssm_inner == cfg["mamba_expand"] * cfg["hidden_size"] == 5120
    assert (toy.ssm_dt_rank, toy.ssm_state_size, toy.conv_kernel) == (
        cfg["mamba_dt_rank"], cfg["mamba_d_state"], cfg["mamba_d_conv"])
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    assert chip_smoke.MIXER_LAYERS == sum(
        l % period != offset for l in range(cfg["num_hidden_layers"]))
    assert chip_smoke.MIXER_ROWS == cfg["serve"]["batch_per_slot"]
