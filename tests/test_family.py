"""The record of a model family (``models/family.py``): every preset a block
file serves gets its family's code from every site that used to compare
``model_type``, every refusal reads as it did, and the seam stays shut."""

import ast
import pathlib

import pytest

from llm_sharding_tpu.models import config
from llm_sharding_tpu.models.config import ModelConfig
from llm_sharding_tpu.models.family import Family, family, refuse_axes
from llm_sharding_tpu.parallel.pipeline import model_fns
from llm_sharding_tpu.runtime.generate import forward_fn_for
from llm_sharding_tpu.utils.convert import params_from_hf

PACKAGE = pathlib.Path(config.__file__).parent.parent

# one a model_type, the flag-told members of "llama", and the published sizes
# the benchmark's cells run
PRESETS = [
    "tiny_llama", "tiny_gpt2", "tiny_deepseek_v3", "tiny_mimo_v2",
    "tiny_nemotron_h", "tiny_jamba", "tiny_solar_open2", "tiny_longcat_flash",
    "tiny_keye_vl2", "tiny_ouro", "tiny_olmoe", "tiny_qwen2", "tiny_gemma",
    "qwen25_7b", "olmoe_1b_7b", "nemotron3_super_120b_a12b", "jamba2_3b",
    "gpt2_small", "llama32_3b",
]

# the refusals as the six block files raised them, letter for letter
AXES = {
    "deepseek_v3": "tensor / context parallelism over deepseek_v3 (latent "
                   "attention, a share of the experts) is not implemented",
    "mimo_v2": "tensor / context parallelism over mimo_v2 (a KV state per "
               "kind of layer, a share of the experts) is not implemented",
    "nemotron_h": "tensor / context parallelism over nemotron_h (a recurrent "
                  "state beside the arena, a share of the experts) is not "
                  "implemented",
    "jamba": "tensor / context parallelism over jamba (a recurrent state "
             "beside the arena) is not implemented",
    "solar_open2": "tensor / context parallelism over solar_open2 (a "
                   "recurrent matrix state beside the arena, a share of the "
                   "experts) is not implemented",
    "longcat_flash": "tensor / context parallelism over longcat_flash (two "
                     "latent attentions a layer, a share of the experts) is "
                     "not implemented",
}
NO_ORACLE = {"nemotron_h", "jamba", "solar_open2"}
# what the converter says of a family whose checkpoint names it does not hold
UNMAPPED = {
    "tiny_solar_open2": (
        "model_type 'solar_open2': the names of a Solar-Open2 "
        "checkpoint's tensors (a KDA mixer's projections, low-rank pairs, "
        "conv and norm leaves; the attention layers' gate) are in no file "
        "of this repository — the converter maps it once they are; the "
        "block runs on seeded weights (benchmark/blocks/solar_open2.py)"
    ),
    "tiny_longcat_flash": (
        "model_type 'longcat_flash': the names of a LongCat-Flash "
        "checkpoint's tensors (a layer's two attentions, two dense MLPs "
        "and four norms, its router's classifier and correction bias) "
        "are in no file of this repository — the converter maps it once "
        "they are; the block runs on seeded weights "
        "(benchmark/blocks/longcat_flash.py)"
    ),
    "tiny_keye_vl2": "model_type 'KeyeVL2': the names of a Keye checkpoint",
    "tiny_ouro": "model_type 'ouro': the names of an Ouro checkpoint",
}


def _cfg(preset: str) -> ModelConfig:
    return getattr(config, preset)()


def _closed_over(fn) -> set:
    return {c.cell_contents for c in fn.__closure__ if callable(c.cell_contents)}


@pytest.mark.parametrize("preset", PRESETS)
def test_the_ring_runs_the_familys_stage_functions(preset):
    cfg = _cfg(preset)
    fam = family(cfg)
    assert isinstance(fam, Family)
    fns = model_fns(cfg)
    assert fam.forward_layers in _closed_over(fns.stage)
    assert fam.forward_layers_paged in _closed_over(fns.stage_paged)
    assert fns.prefill_walks is fam.prefill_walks
    # the work lists belong to the models whose layers do not all attend alike
    assert (fam.prefill_walks is not None) == (
        cfg.model_type in AXES and cfg.model_type != "deepseek_v3"
    )


@pytest.mark.parametrize("preset", PRESETS)
@pytest.mark.parametrize("axes", [
    {"tp_axis": "tensor"}, {"cp_axis": "cp"},
    {"tp_axis": "tensor", "cp_axis": "cp"},
])
def test_an_axis_a_family_does_not_take_is_refused_as_it_was(preset, axes):
    cfg = _cfg(preset)
    if cfg.model_type in AXES:
        for refuse in (
            lambda: model_fns(cfg, **axes), lambda: refuse_axes(cfg, **axes)
        ):
            with pytest.raises(NotImplementedError) as e:
                refuse()
            assert str(e.value) == AXES[cfg.model_type]
    elif cfg.model_type == "gpt2" and "cp_axis" in axes:
        with pytest.raises(NotImplementedError) as e:
            model_fns(cfg, **axes)
        assert str(e.value) == (
            "context-parallel serving supports the llama family only"
        )
    else:
        assert model_fns(cfg, **axes).stage_paged is not None
        refuse_axes(cfg, **axes)


@pytest.mark.parametrize("preset", PRESETS)
def test_the_oracle_is_the_familys_or_refused_by_name(preset):
    cfg = _cfg(preset)
    if cfg.model_type in NO_ORACLE:
        assert family(cfg).forward is None
        with pytest.raises(NotImplementedError, match=cfg.model_type):
            forward_fn_for(cfg)
    else:
        assert forward_fn_for(cfg) is family(cfg).forward is not None


@pytest.mark.parametrize("preset", PRESETS)
def test_the_converter_maps_the_family_or_refuses_it_as_it_did(preset):
    cfg = _cfg(preset)
    fam = family(cfg)
    if preset in UNMAPPED:
        with pytest.raises(NotImplementedError) as e:
            params_from_hf(cfg, {})
        assert str(e.value).startswith(UNMAPPED[preset])
        if fam.unmapped:
            assert str(e.value) == fam.unmapped == UNMAPPED[preset]
            assert fam.layer_arrays is None
    else:
        assert fam.unmapped == "" and callable(fam.layer_arrays)
        assert fam.learned_positions or len(fam.head_names) == 2
        with pytest.raises(KeyError):  # it asks the source for its first name
            params_from_hf(cfg, {})


@pytest.mark.parametrize("preset", PRESETS)
def test_what_the_parallel_paths_ask_of_a_family(preset):
    cfg = _cfg(preset)
    fam = family(cfg)
    llama, gpt2 = cfg.model_type == "llama", cfg.model_type == "gpt2"
    assert (fam.tp_specs is not None) == (llama or gpt2)
    assert (fam.attn_mlp_block is not None) == (llama or gpt2)
    assert fam.presplit == fam.paged_cp == llama
    assert fam.learned_positions == fam.final_layer_norm == gpt2
    assert (fam.tp_permute is not None) == gpt2
    from llm_sharding_tpu.parallel.pipeline import stage_layer_specs

    if fam.tp_specs is None:
        with pytest.raises(NotImplementedError) as e:
            stage_layer_specs(cfg, 2)
        assert str(e.value) == f"pp×tp: {cfg.model_type!r} unsupported"
    else:
        assert set(stage_layer_specs(cfg, 2)) == set(
            fam.tp_specs(stacked=False)["layers"]
        )


@pytest.mark.parametrize("ask", [
    family, model_fns, forward_fn_for, lambda cfg: params_from_hf(cfg, {}),
])
def test_an_unknown_model_type_is_the_one_value_error(ask):
    cfg = ModelConfig(model_type="bert")
    with pytest.raises(ValueError) as e:
        ask(cfg)
    assert str(e.value) == "unsupported model_type: 'bert'"


def _is_model_type(node) -> bool:
    return isinstance(node, ast.Attribute) and node.attr == "model_type"


def test_no_module_outside_models_compares_or_looks_up_a_model_type():
    """The seam stays shut: outside ``models/`` no ``Compare`` and no
    ``Subscript`` has ``….model_type`` as an operand (printing the name is
    not a comparison); inside it only ``config.py`` and ``family.py`` do."""
    found = []
    for path in sorted(PACKAGE.rglob("*.py")):
        rel = path.relative_to(PACKAGE).as_posix()
        if rel in ("models/config.py", "models/family.py"):
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Compare):
                operands = [node.left, *node.comparators]
            elif isinstance(node, ast.Subscript):
                operands = [node.slice]
            else:
                continue
            if any(_is_model_type(o) for o in operands):
                found.append(f"{rel}:{node.lineno}")
    assert found == []


def test_no_block_file_imports_anothers_private_name():
    """``Run``, ``stage_runs``, the run scan, the stats' placement and the
    zero stats live once, in ``models/stack.py``; a block file takes public
    sub-blocks of another and nothing with an underscore."""
    models = PACKAGE / "models"
    blocks = {p.stem for p in models.glob("*.py")} - {
        "__init__", "cache", "config", "family", "stack",
    }
    found, defined = [], {}
    for path in sorted(models.glob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if (isinstance(node, ast.ImportFrom) and node.level == 1
                    and node.module in blocks):
                found += [
                    f"{path.name}: {node.module}.{a.name}"
                    for a in node.names if a.name.startswith("_")
                ]
        for node in tree.body:
            if isinstance(node, (ast.ClassDef, ast.FunctionDef)):
                defined.setdefault(node.name, []).append(path.stem)
    assert found == []
    for name in ("Run", "stage_runs", "scan_run", "place_stats", "zero_stats"):
        assert defined[name] == ["stack"], name
    assert "_refuse_tp" not in defined and defined["refuse_axes"] == ["family"]
