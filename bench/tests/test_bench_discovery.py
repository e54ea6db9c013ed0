"""Everything ``BENCHMARK.json`` names is found by name, and the file keeps
to the shape the harness reads."""
import json
import re

import pytest

from bench import harness, traffic

BENCH = json.loads(harness.BENCHMARK_JSON.read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = harness.find_cell(cell)
    assert c.config["name"] == \
        {w["name"]: w for w in BENCH["workloads"]}[cell]["config"]
    assert c.limits["max_logit_gap"]["limit"] > 0
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in names


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(harness.metric_reader(m["name"]))
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric")


CONFIG_FILES = sorted((harness.BENCH / "configs").glob("*.json"))


def _named(data, key):
    """The module the configuration names under ``key``, which lies in the
    benchmark's own directories."""
    assert data[key].split("/")[0] in BENCH["paths"]
    return harness.load_module(harness.ROOT / data[key],
                               f"test_{key}_{data['name']}")


def _departures(data, ref):
    """The published keys whose value the ``model`` block departs from,
    by the mapping of the configuration's own reference."""
    return {pub for key, (pub, want)
            in ref.published_block(data["published"]).items()
            if data["model"][key] != want}


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_config_file_is_the_programs_model(path):
    """The file states what the program serves, as its own reference reads
    it, and lists every key it cuts from the published config: a later
    change to the program's config fails here, not on the chip."""
    from repro.configs import get_config
    data = json.loads(path.read_text())
    assert data["name"] == path.stem
    for conf in BENCH["configs"]:
        if conf["name"] == data["name"]:
            assert conf["file"] == f"bench/configs/{path.name}"
            assert data["source"] == conf["source"]
            assert conf["reduced"] == data["reduced"]
    ref = _named(data, "reference")
    assert callable(_named(data, "work").decode_bytes)
    assert harness.program_config(data, ref) is get_config(data["arch"])
    assert _departures(data, ref) <= set(data["reduced"])


def test_a_cut_configuration_must_list_its_cuts():
    data = json.loads((harness.BENCH / "configs"
                       / "granite-3-2b.json").read_text())
    ref = _named(data, "reference")
    assert _departures(data, ref) == set()
    cut = dict(data, model=dict(data["model"], n_layers=10))
    assert _departures(cut, ref) == {"num_hidden_layers"}
    with pytest.raises(ValueError, match="n_layers"):
        harness.program_config(cut, ref)


@pytest.mark.parametrize("mix", sorted({w["traffic"]
                                        for w in BENCH["workloads"]}))
def test_mix_resolves(mix):
    assert traffic.load_mix(mix)["name"] == mix
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("no-such-mix")


def test_unknown_cell_and_device_are_errors():
    with pytest.raises(KeyError):
        harness.find_cell("no-such-cell")
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v0")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
