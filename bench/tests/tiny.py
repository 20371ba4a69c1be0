"""A tiny deployment for the self-tests: a configuration of the same
schema and templates at a small population, derived from a benchmark
configuration file with the program's catalog rules, and tiny traffic."""
from __future__ import annotations

import copy
import json
import os
import types

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(rel: str):
    with open(os.path.join(BENCH, rel)) as f:
        return json.load(f)


def tiny_config(name: str = "tpcw-10k-100eb-noidx", items: int = 512,
                customers: int = 2880):
    """The configuration ``name`` at ``items`` x ``customers``: the same
    tables and templates, the capacities the program derives."""
    from repro.workloads import tpcw
    cfg = copy.deepcopy(load(f"configs/{name}.json"))
    cat = tpcw.make_catalog(items, customers, cfg["headroom"],
                            dense_pk_index=cfg["dense_pk_index"])
    cfg.update(items=items, customers=customers)
    for t, spec in cfg["tables"].items():
        spec["capacity"] = cat.schemas[t].capacity
    cfg["templates"]["best_sellers"]["group"]["groups"] = \
        cat.schemas["item"].capacity
    return cfg


def tiny_traffic(name: str = "shopping-sat", ebs: int = 8,
                 think_mean_s: float = 0.05):
    trf = copy.deepcopy(load(f"traffic/{name}.json"))
    trf["emulated_browsers"] = ebs
    if trf["think"]["dist"] != "none":
        trf["think"] = {"dist": "neg_exp", "mean_s": think_mean_s,
                        "max_s": 10 * think_mean_s}
    trf["trace_s"] = 0.5
    return trf


def tiny_cell(config=None, traffic=None):
    return types.SimpleNamespace(name="tiny", chips=1,
                                 config=config or tiny_config(),
                                 traffic=traffic or tiny_traffic())
