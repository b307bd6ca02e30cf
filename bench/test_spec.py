"""BENCHMARK.json against the files the harness finds by name."""
import json
import os

import pytest

import model as M
import run as R
import traffic as TF

SPEC = R.load_spec()


def test_every_metric_has_a_reader():
    for kind in ("end_to_end", "per_layer"):
        for m in SPEC[kind]:
            assert callable(R.reader(m["name"])), m["name"]


def test_every_cell_names_existing_files():
    for w in SPEC["workloads"]:
        cfg = M.load_config(w["config"])
        assert cfg["name"] == w["config"]
        TF.load_mix(w["traffic"])
        assert M.load_reference(cfg).logits_at


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_names_a_layout(c):
    layout = M.load_layout(M.load_config(c["name"]))
    for fn in ("program_config", "make_weights", "linear_shapes",
               "matmul_shapes"):
        assert callable(getattr(layout, fn)), fn


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry_matches_its_file(c):
    path = os.path.join(R.ROOT, c["file"])
    with open(path) as f:
        cfg = json.load(f)
    assert cfg["name"] == c["name"]
    assert cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"]
    for key in cfg["reduced"]:
        assert key in cfg.get("published", {})
