"""BENCHMARK.json and the files it names are sound."""

import copy
import os

from perf import harness


def test_manifest_is_sound():
    assert harness.check_manifest() == []


def test_every_cell_loads_with_its_files():
    manifest = harness.load_manifest()
    for entry in manifest["workloads"]:
        cell = harness.load_cell(entry["name"], manifest)
        assert cell.kind in ("train", "serve")
        assert cell.config["name"] == entry["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer and set(cell.layer_files) == {
            m["name"] for m in cell.per_layer}


def test_files_under_paths_have_contract_names():
    for base, _dirs, files in os.walk(harness.PERF_DIR):
        if "__pycache__" in base or ".pytest_cache" in base:
            continue
        for name in files:
            assert harness.NAME_RE.match(name), os.path.join(base, name)


def test_check_catches_faults():
    good = harness.load_manifest()

    def broken(edit):
        m = copy.deepcopy(good)
        edit(m)
        return harness.check_manifest(m)

    # a metric that moves something its cells do not report
    assert broken(lambda m: m["per_layer"][1].update(
        moves="serve_tokens_per_s"))
    assert broken(lambda m: m["per_layer"][1].update(moves="ttft_ms_p95"))
    # a second four-chip cell of three
    assert broken(lambda m: m["workloads"][0].update(chips=4))
    # a unit with a space, a name with a slash, a loose bound
    assert broken(lambda m: m["end_to_end"][0].update(unit="tokens per s"))
    assert broken(lambda m: m["end_to_end"][0].update(name="a/b"))
    assert broken(lambda m: m["end_to_end"][0].update(bound=0.5))
    # a key the contract does not know
    assert broken(lambda m: m["per_layer"][0].update(why="because"))


def test_unknown_option_is_an_error():
    import pytest

    with pytest.raises(harness.ManifestError):
        harness.take_options({"per_chip_batch": 4, "bogus": 1},
                             {"per_chip_batch": None}, "test")
