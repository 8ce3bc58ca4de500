"""Where the benchmark's data files are, and how a configuration's file
becomes the layer list that is run.

Everything the harness knows about a cell, a configuration or a metric it
finds by name under this directory; nothing here names one.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name, directory=None):
    """The cell's own file, ``workloads/<name>.json``."""
    path = os.path.join(directory or os.path.join(HERE, "workloads"),
                        name + ".json")
    with open(path) as f:
        return json.load(f)


def load_config(name, directory=None):
    path = os.path.join(directory or os.path.join(HERE, "configs"),
                        name + ".json")
    with open(path) as f:
        return json.load(f)


def _subst(value, cfg, i=None):
    """``"$key"`` -> the configuration's top-level number of that name;
    ``"{i}"`` inside a string -> the block's index."""
    if isinstance(value, str):
        if value.startswith("$"):
            return cfg[value[1:]]
        return value.replace("{i}", str(i)) if i is not None else value
    if isinstance(value, list):
        return [_subst(v, cfg, i) for v in value]
    if isinstance(value, dict):
        return {k: _subst(v, cfg, i) for k, v in value.items()}
    return value


def expand_layers(cfg):
    """The configuration's layer list as plain dicts.

    ``workflow.layers`` is either the list itself or
    ``{"first": [...], "block": [...], "repeat": n, "last": [...]}``, the
    block repeated with ``{i}`` in its names replaced by 0..n-1.  A value
    ``"$key"`` is the file's top-level ``key``, so a width is written once.
    """
    spec = cfg["workflow"]["layers"]
    if isinstance(spec, list):
        return [_subst(layer, cfg) for layer in spec]
    layers = [_subst(layer, cfg) for layer in spec.get("first", [])]
    for i in range(int(_subst(spec["repeat"], cfg))):
        layers += [_subst(layer, cfg, i) for layer in spec["block"]]
    layers += [_subst(layer, cfg) for layer in spec.get("last", [])]
    return layers


def input_spec(cfg, traffic):
    """Shape of one batch's ``@input`` as the first layer sees it."""
    data = cfg["data"]
    if data["kind"] == "image_store":
        hw = int(data["crop_hw"])
        return (int(traffic["batch"]), hw, hw, int(data["channels"]))
    if data["kind"] == "token_rows":
        return (int(traffic["batch"]), int(traffic["seq_len"]))
    raise ValueError(f"unknown data kind {data['kind']!r}")


def items_per_row(cfg, traffic):
    """Images per row are 1; tokens per row are the sequence length."""
    return int(traffic["seq_len"]) if cfg["data"]["kind"] == "token_rows" \
        else 1
