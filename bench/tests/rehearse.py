"""Shared by the CPU tests that drive a whole run: the tiny
configuration and cell beside this file, through the harness's window
loop and check, with the look for a chip skipped."""

import json
import os
import time

from bench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "stablelm-1.6b-zoo.recognize-steady"


def tiny():
    return (harness.load_json(os.path.join(HERE, "tiny-zoo.json")),
            harness.load_json(os.path.join(HERE, "tiny-cell.json")))


def run(seed=2 ** 31 + 12345, seconds=3.0, one_token=False, mesh=None,
        only=None, **kw):
    """One run of the tiny cell; `one_token` asks one output token of
    every request, as the recognition mix does; `mesh` ([data, model])
    runs the configuration on a mesh of that shape; `only` keeps that
    one candidate of the configuration, and the limits of its numbers."""
    cfg, cell = tiny()
    if one_token:
        cell["mix"]["output_tokens"] = {"min": 1, "max": 1}
    if only is not None:
        cfg["models"] = {only: cfg["models"][only]}
        cell["limits"] = {k: v for k, v in cell["limits"].items()
                          if k.endswith(f".{only}")}
    if mesh is not None:
        cfg["mesh"] = {"shape": list(mesh), "axes": ["data", "model"]}
    return harness.run("tiny", seed, seconds, False,
                       t_start=time.perf_counter(), cfg=cfg, cell=cell,
                       entries=harness.metric_entries(CELL, False), **kw)


def last_line(result) -> dict:
    return json.loads(json.dumps(result))


def on_mesh(shape=(1, 4), seed=2 ** 31 + 4321) -> dict:
    """Run in a process whose XLA_FLAGS force the host's devices before
    JAX starts. Where the program's rules put each candidate's stacked
    wq and where the harness put it; one whole run of the tiny cell on
    the mesh, with the control, in which both candidates served; and one
    run with the exchange between chips left out."""
    from repro.models import param_logical_axes
    from repro.sharding import tree_specs

    from bench import faults
    cfg, _ = tiny()
    cfg["mesh"] = {"shape": list(shape), "axes": ["data", "model"]}
    par = harness.parallel_of(cfg)
    placed = {}
    for name, eng in harness.build_engines(cfg, seed).items():
        rule = tree_specs(param_logical_axes(eng.cfg), par,
                          eng.cfg)["blocks"][0]["wq"]
        w = eng.params["blocks"][0]["wq"]
        w = w["q"] if isinstance(w, dict) else w
        placed[name] = {"rule": str(rule), "spec": str(w.sharding.spec),
                        "devices": len(w.sharding.device_set),
                        "parallel": eng.parallel is not None}
    for _ in range(4):
        result = last_line(run(seed, control=True, mesh=shape))
        if not any(c.get("unserved") for c in result["checks"].values()):
            break
    faults.plant("exchange_left_out")
    faulty = last_line(run(seed, mesh=shape))
    return {"placed": placed, "result": result, "faulty": faulty}


if __name__ == "__main__":
    print(json.dumps(on_mesh()), flush=True)
