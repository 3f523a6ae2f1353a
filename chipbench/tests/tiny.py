"""Tiny stand-ins of the cells, for runs on the CPU in the tests: each
family's `tiny` sizes (the program's own `--reduced` ones: 2 layers,
width 64, vocabulary 512)."""

import copy
import json
import pathlib

from chipbench import harness

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "chipbench"


def _load(path):
    with open(path) as f:
        return json.load(f)


def tiny_cfg(name="gpt2-small"):
    cfg = copy.deepcopy(_load(BENCH / "configs" / f"{name}.json"))
    cfg = harness.load_family(cfg["model_type"]).tiny(cfg)
    cfg["program_reduced"] = True
    cfg["lora"] = dict(cfg["lora"], r_others=4, r_cut=2, cut_layer=1)
    return cfg


def tiny_train_traffic():
    tr = _load(BENCH / "traffic" / "train.paper.json")
    return dict(tr, clients=3, batch=2, seq_len=64, corpus_samples=64)


def tiny_serve_traffic():
    tr = _load(BENCH / "traffic" / "serve.steady.json")
    return dict(tr, adapters=4, slots=4, max_len=64,
                prompt=dict(tr["prompt"], median=12, min=4, max=40),
                output=dict(tr["output"], median=6, min=2, max=20),
                rate_per_s=40.0, check_requests=3)


LATER = [
    {"name": "gpt2s.train.fleet4", "config": "gpt2-small",
     "traffic": "train.fleet4", "chips": 4,
     "why": "the client-sharded round, kept for a later benchmark PR"},
    {"name": "gpt2s.serve.steady", "config": "gpt2-small",
     "traffic": "serve.steady", "chips": 1,
     "why": "the serving cell, kept for a later benchmark PR"},
]


def bench_with_later():
    """BENCHMARK.json with the cells that PERF.md keeps for later, so
    their paths can be driven on the CPU."""
    bench = copy.deepcopy(_load(BENCH.parent / "BENCHMARK.json"))
    have = {w["name"] for w in bench["workloads"]}
    bench["workloads"] += [dict(w) for w in LATER if w["name"] not in have]
    return bench
