"""Every configuration file that names a model of the catalog beside the
``model-configs`` guide holds that model's published ``config`` at its TOP
level, where the driver reads it (a key inside a ``model`` group reads as
null there: PR 36's first refusal): each key with the row's value, unless
``reduced`` lists it, and ``BENCHMARK.json`` lists the same keys as
reduced. A file that gives a key another value (or leaves it out) without
saying so is refused by the driver before any run; this finds it on the
CPU. Skipped where the catalog is absent."""

import glob
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _configs():
    return sorted(glob.glob(os.path.join(ROOT, "benchmarks", "configs",
                                         "*.json")))


def _catalog():
    if not os.path.isfile(CATALOG):
        pytest.skip(f"no catalog at {CATALOG}")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return {r["source_url"]: r for r in rows}


def _is_width(key: str) -> bool:
    """What ``reduced`` may never name: a hidden, intermediate, latent,
    state or projection size, a head size, an expansion factor, the
    experts a token takes. Counts of layers, experts and vocabulary rows
    are what a chip's share may cut."""
    return (key.endswith(("_dim", "_rank")) or "head_dim" in key
            or "expand" in key or key == "num_experts_per_tok"
            or (key.endswith("_size") and key != "vocab_size"))


@pytest.mark.parametrize("path", _configs(), ids=os.path.basename)
def test_a_catalog_models_file_holds_its_published_config(path):
    catalog = _catalog()
    with open(path) as f:
        config = json.load(f)
    row = catalog.get(config["source"])
    if row is None:
        pytest.skip(f"{config['source']} is not a source_url of the catalog")
    reduced = config["reduced"]
    for key, published in row["config"].items():
        assert key in config, f"{key} is left out"
        if key in reduced:
            assert config[key] != published, (
                f"{key} is listed as reduced and is as published")
        else:
            assert config[key] == published, (
                f"{key}: the file gives {config[key]!r}, its "
                f"source {published!r}, and reduced does not list it")
    assert set(reduced) <= set(row["config"])
    assert not [k for k in reduced if _is_width(k)], "a width may not change"


@pytest.mark.parametrize("path", _configs(), ids=os.path.basename)
def test_benchmark_json_lists_the_files_reduced_keys(path):
    with open(path) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {c["file"]: c for c in json.load(f)["configs"]}
    entry = entries[os.path.relpath(path, ROOT)]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["name"] == config["name"]
