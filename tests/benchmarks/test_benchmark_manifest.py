"""BENCHMARK.json keeps the contract's rules that need no run, and the
harness finds every file a cell names."""
import copy
import json
import os

import pytest

import bench_paths
from harness import loadgen, manifest as mf


@pytest.fixture(scope="module")
def manifest():
    return mf.load(os.path.join(bench_paths.REPO, "BENCHMARK.json"))


def test_the_manifest_is_valid(manifest):
    assert mf.problems(manifest, bench_paths.REPO) == []
    assert len(json.dumps(manifest)) < 64 * 1024


def test_every_cell_finds_its_files(manifest):
    for w in manifest["workloads"]:
        cell = mf.cell(manifest, w["name"])
        assert os.path.isfile(os.path.join(bench_paths.REPO,
                                           cell["config"]["file"]))
        mix = mf.load(mf.traffic_file(manifest, bench_paths.REPO,
                                      w["traffic"]))
        assert os.path.isfile(mf.kind_file(manifest, bench_paths.REPO,
                                           mix["kind"]))
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        for m in cell["per_layer"]:
            assert os.path.isfile(mf.reader_file(
                manifest, bench_paths.REPO, m["name"]))
            assert m["moves"] in names


def test_every_configuration_file_states_its_source_and_sizes(manifest):
    for c in manifest["configs"]:
        with open(os.path.join(bench_paths.REPO, c["file"]),
                  encoding="utf-8") as f:
            config = json.load(f)
        assert config["name"] == c["name"]
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        for key in ("hidden_size", "intermediate_size",
                    "num_hidden_layers", "num_attention_heads",
                    "num_key_value_heads", "vocab_size", "engine"):
            assert key in config
        assert os.path.isfile(os.path.join(
            os.path.dirname(os.path.join(bench_paths.REPO, c["file"])),
            config["reference"] + ".py"))


def test_tokens_per_s_only_where_the_loop_is_closed(manifest):
    """Below the knee at a fixed rate, completed tokens per second is
    the offer: it is a metric of closed-loop cells only."""
    tps = next(m for m in manifest["end_to_end"]
               if m["name"] == "tokens_per_s")
    everywhere = [w["name"] for w in manifest["workloads"]]
    for w in manifest["workloads"]:
        with open(mf.traffic_file(manifest, bench_paths.REPO,
                                  w["traffic"]), encoding="utf-8") as f:
            kind = loadgen.load_kind(mf.kind_file(
                manifest, bench_paths.REPO, json.load(f)["kind"]))
        closed = kind.LOOP == "closed"
        assert (w["name"] in tps.get("workloads", everywhere)) == closed


def _broken(manifest, edit):
    m = copy.deepcopy(manifest)
    edit(m)
    return mf.problems(m, bench_paths.REPO)


BREACHES = {
    "unit with a space": lambda m: m["end_to_end"][0].update(
        unit="tokens per s"),
    "unit over 16 characters": lambda m: m["per_layer"][0].update(
        unit="milliseconds/token"),
    "greek letter in a unit": lambda m: m["per_layer"][0].update(
        unit="µs"),
    "name with a slash": lambda m: m["per_layer"][0].update(name="a/b"),
    "two metrics of one name": lambda m: m["per_layer"].append(
        dict(m["per_layer"][0])),
    "moves a per-layer metric": lambda m: m["per_layer"][0].update(
        moves="sched.rows_per_segment"),
    "moves a metric its cell lacks": lambda m: (
        next(e for e in m["end_to_end"] if e["name"] == "tokens_per_s")
        ["workloads"].pop(),
        m["per_layer"][0].update(moves="tokens_per_s")),
    "bound over a tenth": lambda m: m["end_to_end"][0].update(bound=0.2),
    "no setup_s": lambda m: m["end_to_end"].pop(),
    "a configuration without a cell": lambda m: m["workloads"].pop(),
    "a cell without its configuration": lambda m: m["configs"].pop(),
    "a pair twice": lambda m: m["workloads"].append(
        dict(m["workloads"][0], name="again")),
    "three chips": lambda m: m["workloads"][0].update(chips=3),
    "a why on a metric": lambda m: m["per_layer"][0].update(why="x"),
    "a width reduced": lambda m: m["configs"][0].update(
        reduced=["hidden_size"]),
    "a head size reduced": lambda m: m["configs"][0].update(
        reduced=["head_dim"]),
    "a configuration file outside paths": lambda m: m["configs"][0]
    .update(file="theroundtaible_tpu/engine/models/registry.py"),
    "an absolute command": lambda m: m["command"].append("/bin/sh"),
    "a command that leaves the repo": lambda m: m["command"].append(
        "../x.py"),
    "run_seconds of 52": lambda m: m.update(run_seconds=52),
    "an unknown source": lambda m: m["per_layer"][0].update(
        source="guess"),
    "program_counter end to end": lambda m: m["end_to_end"][0].update(
        source="program_counter"),
    "better sideways": lambda m: m["per_layer"][0].update(
        better="sideways"),
    "an extra top-level key": lambda m: m.update(notes="x"),
    "a metric of an unknown cell": lambda m: m["per_layer"][0].update(
        workloads=["nowhere"]),
    "a why of two lines": lambda m: m["workloads"][0].update(
        why="a\nb"),
}


@pytest.mark.parametrize("breach", sorted(BREACHES))
def test_the_checks_catch(manifest, breach):
    assert _broken(manifest, BREACHES[breach]), breach


def test_a_fifth_cell_may_not_be_a_second_four_chip_cell(manifest):
    def edit(m):
        for i in range(3):
            m["workloads"].append(dict(
                m["workloads"][0], name=f"more{i}", traffic=f"t{i}",
                chips=4 if i < 2 else 1))
    assert any("four chips" in p for p in _broken(manifest, edit))


def test_an_unknown_cell_or_metric_is_an_error(manifest):
    with pytest.raises(SystemExit):
        mf.cell(manifest, "no-such-cell")
    with pytest.raises(SystemExit):
        mf.reader_file(manifest, bench_paths.REPO, "no.such.metric")
    with pytest.raises(SystemExit):
        mf.traffic_file(manifest, bench_paths.REPO, "no-such-mix")
