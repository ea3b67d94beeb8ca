"""BENCHMARK.json against the files it names and the limits of its form."""

import json
import os
import re

import pytest

from benchmark import spec

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(bench["run_seconds"], int) \
        and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_every_name_and_unit_is_well_formed(bench):
    names = [e["name"] for kind in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in bench[kind]]
    names += [w[k] for w in bench["workloads"] for k in ("config", "traffic")]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.fullmatch(name), name
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            assert UNIT.fullmatch(metric["unit"]), metric
            assert metric["better"] in ("lower", "higher")
            assert metric["source"] in SOURCES
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        listed = [e["name"] for e in bench[kind]]
        assert len(listed) == len(set(listed))
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_entries_have_just_the_contracts_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_cells(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        mine = {m["name"] for m in spec.metrics_of(bench, "end_to_end",
                                                   w["name"])}
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        layers = spec.metrics_of(bench, "per_layer", w["name"])
        assert layers, w["name"]
        for m in layers:   # what a layer's metric moves is reported there
            assert m["moves"] in mine, (w["name"], m["name"])
    cells = {w["name"] for w in bench["workloads"]}
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert set(m.get("workloads", [])) <= cells, m["name"]


def test_a_layer_has_one_spelling(bench):
    layers = {m["layer"] for m in bench["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)
    for layer in layers:
        assert 1 <= len(layer) <= 200 and "\n" not in layer


def test_everything_named_resolves_to_a_file(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(bench, w["name"])
        assert cell["config"]["family"]
        generator = spec.load_part("generators",
                                   cell["traffic"]["generator"])
        assert callable(generator.run)
        family = spec.load_part("families", cell["config"]["family"])
        assert callable(family.program_config) and callable(family.init)
        assert callable(family.reference_forward)
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert callable(spec.metric_reader(m["name"])), m["name"]
    with pytest.raises(SystemExit):
        spec.metric_reader("no_such_metric")
    with pytest.raises(SystemExit):
        spec.load_cell(bench, "no-such-workload")


def test_configuration_files_say_how_they_were_cut(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        assert set(c["reduced"]) <= set(config["assumed"])
        assert config["deployment"]
        for key in c["reduced"]:    # a width is never cut
            assert not re.search(r"_dim$|_rank$|hidden_size|"
                                 r"intermediate_size|n_embd|head", key)


def test_published_widths():
    mistral = spec.load_json("configs", "mistral-7b-v0.3-8l.json")
    assert (mistral["hidden_size"], mistral["intermediate_size"],
            mistral["num_attention_heads"], mistral["num_key_value_heads"],
            mistral["head_dim"], mistral["vocab_size"]) == \
        (4096, 14336, 32, 8, 128, 32768)
    engine = mistral["engine"]
    pages_per_sequence = (engine["max_prompt_len"]
                          + engine["max_new_tokens"]) // engine["page_size"]
    assert engine["num_pages"] == engine["max_batch"] * pages_per_sequence + 1
    medium = spec.load_json("configs", "gpt2-medium.json")
    large = spec.load_json("configs", "gpt2-large.json")
    assert (medium["n_embd"], medium["n_layer"], medium["n_head"]) == \
        (1024, 24, 16)
    assert (large["n_embd"], large["n_layer"], large["n_head"]) == \
        (1280, 36, 20)
    assert medium["vocab_size"] == large["vocab_size"] == 50257
    assert medium["train"]["batch"] % 4 == large["train"]["batch"] % 4 == 0
