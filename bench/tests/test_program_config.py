"""The seam between a configuration and the program: `program_config`
sets a field of the program's `ModelConfig` from every key of an `archs`
entry, and the configuration's reference names what it does not model."""

import dataclasses
import os

import pytest
from repro.configs import get_config

from bench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(harness.BENCH, "configs")
# The sizes of a dense decoder, which the harness once took by name.
DENSE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "rotary_pct", "rope_theta", "norm_eps",
              "mlp_gated")

# An expert-layer entry at test size; the pattern is a JSON list.
MOE = {"program_config": "qwen3_moe_235b", "pattern": ["moe"],
       "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
       "head_dim": 16, "d_ff": 0, "vocab": 512,
       "moe": {"n_experts": 4, "top_k": 2, "d_ff_expert": 32,
               "capacity_factor": 2.0}}
MOE_CFG = {"name": "tiny-moe", "reference": "moe_stub",
           "archs": {"tiny-moe": MOE}}


class MoEReference:
    """A stand-in reference that models the expert pattern only."""

    @staticmethod
    def unmodelled(program: dict) -> list:
        return [] if tuple(program["pattern"]) == ("moe",) else ["pattern"]


def arch_entries():
    paths = [os.path.join(CONFIGS, f) for f in sorted(os.listdir(CONFIGS))
             if f.endswith(".json")]
    paths.append(os.path.join(HERE, "tiny-zoo.json"))
    for path in paths:
        for name in sorted(harness.load_json(path)["archs"]):
            yield pytest.param(path, name,
                               id=f"{os.path.basename(path)[:-5]}:{name}")


def holds(value, stated) -> bool:
    """`value` (from `dataclasses.asdict`) is what the JSON states."""
    if isinstance(stated, dict):
        return all(holds(value[k], v) for k, v in stated.items())
    if isinstance(stated, list):
        return list(value) == stated
    return value == stated


@pytest.mark.parametrize("path,name", list(arch_entries()))
def test_each_configuration_builds_the_program_config_it_states(path, name):
    cfg = harness.load_json(path)
    arch = cfg["archs"][name]
    got = harness.program_config(cfg, arch)
    stated = {k: v for k, v in arch.items() if k not in harness.NOT_FIELDS}
    program = dataclasses.asdict(got)
    for k, v in stated.items():
        assert holds(program[k], v), k
    if set(stated) == set(DENSE_KEYS):
        want = get_config(arch["program_config"], param_dtype="bfloat16",
                          compute_dtype="bfloat16", attn_impl="pallas")
        want = want.with_runtime(**{k: arch[k] for k in DENSE_KEYS})
        for f in dataclasses.fields(want):
            assert getattr(got, f.name) == getattr(want, f.name), f.name
    assert harness.reference_module(cfg).unmodelled(program) == []


def test_nested_sizes_reach_the_program_config(monkeypatch):
    monkeypatch.setattr(harness, "reference_module",
                        lambda cfg: MoEReference)
    got = harness.program_config(MOE_CFG, MOE)
    assert got.pattern == ("moe",)
    assert (got.n_layers, got.d_model, got.n_heads, got.n_kv_heads,
            got.head_dim, got.d_ff, got.vocab) == (2, 64, 4, 2, 16, 0, 512)
    assert (got.moe.n_experts, got.moe.top_k, got.moe.d_ff_expert,
            got.moe.capacity_factor) == (4, 2, 32, 2.0)
    # what the entry leaves out stays the program's
    assert got.moe.router_dtype == get_config(MOE["program_config"]).moe \
        .router_dtype
    assert got.qk_norm and got.param_dtype == "bfloat16"


def test_the_dense_reference_refuses_the_expert_entry():
    cfg = dict(MOE_CFG, reference="dense_decoder")
    with pytest.raises(ValueError, match="pattern") as e:
        harness.program_config(cfg, MOE)
    assert "tiny-moe" in str(e.value) and "dense_decoder" in str(e.value)
    assert "qk_norm" in str(e.value)


@pytest.mark.parametrize("entry,key", [
    (dict(MOE, d_fff=128), "'d_fff'"),
    (dict(MOE, moe=dict(MOE["moe"], top_kk=2)), "'top_kk'"),
    (dict(MOE, n_layers={"n": 2}), "n_layers"),
], ids=["top level", "nested", "dict for a size"])
def test_a_key_that_names_no_field_is_refused(monkeypatch, entry, key):
    monkeypatch.setattr(harness, "reference_module",
                        lambda cfg: MoEReference)
    with pytest.raises(ValueError, match=key):
        harness.program_config(MOE_CFG, entry)


@pytest.mark.parametrize("field,value", [
    ("pattern", ("local", "global")), ("tie_embeddings", True),
    ("mlp_act", "gelu"), ("mlp_gated", False), ("qk_norm", True),
    ("sandwich_norm", True),
    ("window", 8), ("attn_softcap", 50.0), ("final_softcap", 30.0),
    ("embed_scale", True), ("tp_pad_heads", 64), ("tp_pad_vocab", 100864),
    ("input_mode", "embeddings")])
def test_the_dense_reference_names_each_field_it_does_not_model(field,
                                                                value):
    ref = harness.reference_module({"reference": "dense_decoder"})
    plain = dataclasses.asdict(get_config("stablelm_1_6b"))
    assert ref.unmodelled(plain) == []
    assert ref.unmodelled(dict(plain, **{field: value})) == [field]
