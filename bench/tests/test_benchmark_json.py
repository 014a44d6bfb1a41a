"""BENCHMARK.json against the contract the harness relies on: every name
resolves to its file, every per-layer metric moves an end-to-end metric
its cells report, names and units use the allowed characters, and a new
configuration, mix and metric are found from new files alone."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


def test_every_cell_resolves(bench):
    for cell in bench["workloads"]:
        c, config, mix = harness.load_cell(bench, cell["name"], ROOT)
        assert config["name"] == cell["config"]
        assert cell["chips"] == 1
        assert (mix["batch_rows"]
                == config["pipeline"]["paper_pipeline"]["batch_size"])
        harness.Parts(config, ROOT)
        _, layer = harness.cell_metrics(bench, cell["name"])
        for m in layer:
            assert callable(harness.load_reader(m["name"]))


def test_moves_name_a_metric_each_cell_reports(bench):
    cells = {c["name"] for c in bench["workloads"]}
    for m in bench["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in cells
            e2e, layer = harness.cell_metrics(bench, cell)
            assert m["moves"] in {e["name"] for e in e2e}
            assert m["name"] in {x["name"] for x in layer}
    for cell in cells:
        e2e, layer = harness.cell_metrics(bench, cell)
        assert "setup_s" in {e["name"] for e in e2e}
        assert len(e2e) >= 2 and layer


def test_names_and_units(bench):
    entries = (bench["configs"] + bench["workloads"] + bench["end_to_end"]
               + bench["per_layer"])
    names = [e["name"] for e in entries]
    for kind in ("configs", "workloads"):
        assert len({e["name"] for e in bench[kind]}) == len(bench[kind])
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for n in names:
        assert NAME.match(n), n
    for cell in bench["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in bench["configs"]:
        for key in c["reduced"]:
            assert NAME.match(key)
    for text in ([c["why"] for c in bench["workloads"]]
                 + [c["source"] for c in bench["configs"]]
                 + [m["layer"] for m in bench["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


WIDTHS = ("arch_sparse_feature_size", "arch_mlp_bot", "arch_mlp_top")


def test_configs_follow_their_source(bench):
    """One copy of the widths, the source's own keys: only the keys that
    ``reduced`` lists differ from the source, each with its source value,
    and none is a width; the model runs at the widths read from them."""
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as fh:
            config = json.load(fh)
        assert config["reduced"] == entry["reduced"]
        assert sorted(config["source_values"]) == sorted(entry["reduced"])
        assert sorted(config["reduced_why"]) == sorted(entry["reduced"])
        for key in entry["reduced"]:
            assert key not in WIDTHS and not key.endswith(("_dim", "_rank"))
            assert config[key] != config["source_values"][key]
        w = harness.Parts(config, ROOT).widths
        bot, top = config["arch_mlp_bot"], config["arch_mlp_top"]
        assert [w["n_dense"]] + w["bot_mlp"] == bot
        assert w["top_mlp"] == top
        assert w["d_emb"] == config["arch_sparse_feature_size"] == bot[-1]
        assert w["n_sparse"] == config["num_sparse_features"]
        assert w["vocab_size"] == config["max_ind_range"] + 1


# a Pipeline I reference (hashed ids, no vocabulary), as a later PR would
# add it: a new file beside the others, found by the name a config gives
HASH_REFERENCE = '''"""Pipeline I: ids hashed by a modulus, no vocabulary."""
import numpy as np

from bench.reference import etl


def _modulus(config):
    return int(config["pipeline"]["paper_pipeline"]["modulus"])


def id_rows(config):
    return _modulus(config)


def table_capacities(config):
    return []


def fit(pool, config):
    return []


def apply(raw, tables, config, dtype=np.float32):
    outs = {o["name"]: o["cols"] for o in config["etl_outputs"]}
    ids = etl.sparse_ids(raw, _modulus(config))
    sp = np.zeros((ids.shape[0], outs["sparse"]), np.int32)
    sp[:, :ids.shape[1]] = ids
    return {"dense": etl.dense(raw, outs["dense"], dtype), "sparse": sp,
            "label": raw["label"].astype(np.float32)}
'''


def _copy_with_new_files(bench, tmp_path, etl_reference):
    """A copy of the benchmark with a Pipeline I configuration, a uniform
    mix, a metric and a cell added as new files and entries only."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    new = json.loads(json.dumps(bench))
    src_cfg = bench["configs"][0]
    with open(os.path.join(ROOT, src_cfg["file"])) as fh:
        cfg = json.load(fh)
    cfg["name"] = "dlrm-criteo-hash"
    cfg["pipeline"]["paper_pipeline"] = {"which": "I", "modulus": 131072,
                                         "batch_size": 65536}
    cfg["pipeline"]["lowering"] = {"apply": "grouped", "fit": "fused"}
    cfg["etl_reference"] = etl_reference
    (tmp_path / "bench/configs/dlrm-criteo-hash.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/reference/etl_hash.py").write_text(HASH_REFERENCE)
    mix = json.loads((tmp_path / "bench/traffic/zipf1.3-closed.json")
                     .read_text())
    mix["sparse"] = {"distribution": "uniform", "id_universe": 4194304}
    (tmp_path / "bench/traffic/uniform-closed.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    new["configs"].append(dict(src_cfg, name="dlrm-criteo-hash",
                               file="bench/configs/dlrm-criteo-hash.json"))
    new["workloads"].append({"name": "criteo-hash.uniform",
                             "config": "dlrm-criteo-hash",
                             "traffic": "uniform-closed", "chips": 1,
                             "why": "x"})
    new["per_layer"].append({"name": "new_metric", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "runtime", "moves": "setup_s",
                             "workloads": ["criteo-hash.uniform"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))


@pytest.mark.parametrize("etl_reference", ["etl", "etl_hash"])
def test_new_files_are_found_without_edits(bench, tmp_path, etl_reference):
    """A Pipeline I cell on uniform keys, with its configuration, mix,
    metric and (for ``etl_hash``) ETL reference added as new files, runs
    whole on the CPU at a small size from a copy in which no existing file
    was edited: sound it is correct, with an id altered it is not."""
    _copy_with_new_files(bench, tmp_path, etl_reference)
    for rel in ("bench/harness.py", "bench/reference/etl.py",
                "bench/gen.py", "bench/configs/dlrm-criteo-vocab131k.json"):
        with open(os.path.join(ROOT, rel)) as a:
            assert (tmp_path / rel).read_text() == a.read()
    loaded = harness.load_benchmark(str(tmp_path))
    _, config, mix = harness.load_cell(loaded, "criteo-hash.uniform",
                                       str(tmp_path))
    assert config["etl_reference"] == etl_reference
    assert mix["sparse"]["distribution"] == "uniform"
    _, layer = harness.cell_metrics(loaded, "criteo-hash.uniform")
    assert "new_metric" in {m["name"] for m in layer}
    assert harness.load_reader("new_metric", str(tmp_path))({}) == 42.0
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path),
                                           os.path.join(ROOT, "src")]))
    out = subprocess.run(
        [sys.executable, "-m", "bench.tests.small", "criteo-hash.uniform",
         "sound", "altered_answer"], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "sound": True, "altered_answer": False}
    assert "apply sparse: path=grouped" in out.stderr
