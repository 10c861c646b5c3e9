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


def run(seed=2 ** 31 + 12345, seconds=3.0, one_token=False, **kw):
    """One run of the tiny cell; `one_token` asks one output token of
    every request, as the recognition mix does."""
    cfg, cell = tiny()
    if one_token:
        cell["mix"]["output_tokens"] = {"min": 1, "max": 1}
    return harness.run("tiny", seed, seconds, False,
                       t_start=time.perf_counter(), cfg=cfg, cell=cell,
                       entries=harness.metric_entries(CELL, False), **kw)


def last_line(result) -> dict:
    return json.loads(json.dumps(result))
