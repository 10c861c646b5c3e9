"""A configuration that states a mesh runs on it: the harness places the
weights by the program's sharding rules, serves through the program's
ServingLoop and checks against the reference on four devices of the
host (forced in a process of their own before JAX starts); it refuses a
cell whose chips, devices or weight tree do not fit the mesh. Without a
mesh, the engines run on one device as before."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

from bench import harness
from bench.tests import rehearse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MESH = {"shape": [1, 4], "axes": ["data", "model"]}


@pytest.fixture(scope="module")
def on_mesh():
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        flags + " --xla_force_host_platform_device_count=4").strip())
    p = subprocess.run([sys.executable, "-m", "bench.tests.rehearse"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_run_on_the_mesh_is_correct_with_both_candidates_served(on_mesh):
    r = on_mesh["result"]
    assert r["correct"] is True and r["failed"] == 0
    assert r["device"]["count"] == 4
    for c in r["checks"].values():
        assert not c.get("unserved")
        assert c["value"] is not None and c["value"] <= c["limit"]


def test_weights_lie_where_the_programs_rules_put_them(on_mesh):
    for placed in on_mesh["placed"].values():
        assert placed["parallel"] is True
        assert placed["devices"] == 4
        assert "'model'" in placed["rule"]
        assert placed["spec"] == placed["rule"]


def test_the_exchange_between_chips_left_out_is_not_correct(on_mesh):
    r = on_mesh["faulty"]
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values()
               if c["value"] is not None)


def test_without_a_mesh_engines_run_on_one_device():
    cfg, _ = rehearse.tiny()
    assert harness.chips(cfg) == 1 and harness.parallel_of(cfg) is None
    for eng in harness.build_engines(cfg, 1).values():
        assert eng.parallel is None
        assert all(len(x.sharding.device_set) == 1
                   for x in jax.tree.leaves(eng.params))


def test_a_cell_whose_chips_are_not_the_meshs_is_refused():
    cfg, _ = rehearse.tiny()
    harness.check_chips({"name": "c", "chips": 1}, cfg)
    with pytest.raises(ValueError, match="asks for 4 chip"):
        harness.check_chips({"name": "c", "chips": 4}, cfg)
    cfg["mesh"] = MESH
    harness.check_chips({"name": "c", "chips": 4}, cfg)
    with pytest.raises(ValueError, match="runs on 4"):
        harness.check_chips({"name": "c", "chips": 1}, cfg)


def test_a_mesh_larger_than_the_devices_is_refused():
    cfg, _ = rehearse.tiny()
    cfg["mesh"] = {"shape": [2, 32], "axes": ["data", "model"]}
    with pytest.raises(ValueError, match="needs 64 devices, JAX sees"):
        harness.parallel_of(cfg)


def test_a_reference_tree_other_than_the_programs_is_refused():
    """On a mesh of one device the reference's tree matches the
    program's; with a leaf missing, the refusal names its path."""
    from repro.models import param_logical_axes
    from repro.sharding import tree_shardings, tree_specs
    cfg, _ = rehearse.tiny()
    cfg["mesh"] = {"shape": [1, 1], "axes": ["data", "model"]}
    par = harness.parallel_of(cfg)
    arch = cfg["archs"]["tiny"]
    pcfg = harness.program_config(cfg, arch)
    shardings = tree_shardings(tree_specs(param_logical_axes(pcfg), par,
                                          pcfg), par.mesh)
    ref = harness.reference_module(cfg)
    tree = jax.eval_shape(lambda k: ref.make_weights(arch, k),
                          harness.seed_key(1, 0))
    harness.check_same_tree(cfg, tree, shardings)
    del tree["final_norm"]
    with pytest.raises(ValueError, match=r"differ first at \['final_norm'\]"):
        harness.check_same_tree(cfg, tree, shardings)
    harness.make_weights(cfg, arch, 1, 0, par)


def test_memory_is_the_fullest_devices():
    devs = [SimpleNamespace(memory_stats=lambda b=b: {"peak_bytes_in_use": b})
            for b in (3, 7, 5)]
    assert harness.memory_peak(devs) == 7
    assert harness.memory_peak(devs[:1]) == 3
    assert harness.memory_peak([SimpleNamespace(memory_stats=lambda: None)]) \
        == 0
