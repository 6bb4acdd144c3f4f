"""A traffic mix is a pure function of (parameters, seed, index): the
same seed gives the same traffic, another seed gives the same sizes in
another order. And a mix's kind is a module found by name."""
import json
import os

import pytest

import bench_paths
from harness import loadgen, manifest as mf, traffic

MIXES = ["roundtable", "tiny-table"]
SEEDS = [0, 7, 2_147_483_659, 3_000_000_001]   # the driver's pass 2**31


PATHS = {"paths": ["benchmarks", "tests/benchmarks/rehearsal"]}


def mix(name):
    with open(mf.traffic_file(PATHS, bench_paths.REPO, name),
              encoding="utf-8") as f:
        return json.load(f)


def kind_of(params):
    return loadgen.load_kind(mf.kind_file(PATHS, bench_paths.REPO,
                                          params["kind"]))


discussions = kind_of({"kind": "discussions"})


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_a_session_is_a_pure_function_of_seed_and_index(name, seed):
    params = mix(name)
    kind = kind_of(params)
    for index in (0, 3, 41):
        a = kind.session_spec(params, seed, index)
        b = kind.session_spec(json.loads(json.dumps(params)), seed,
                              index)
        assert a == b
        assert a["session"] == f"bench-{seed}-{index}"


@pytest.mark.parametrize("name", MIXES)
def test_seeds_differ_in_order_and_content_not_in_sizes(name):
    params = mix(name)
    count = params["population"]

    def sizes(seed):
        specs = [kind_of(params).session_spec(params, seed, i)
                 for i in range(count)]
        return ([len(s["opening"]) for s in specs],
                [s["max_new_tokens"] for s in specs],
                [s["opening"] for s in specs])

    p1, n1, c1 = sizes(1)
    p2, n2, c2 = sizes(2)
    assert sorted(p1) == sorted(p2) and sorted(n1) == sorted(n2)
    assert p1 != p2 and c1 != c2
    lo, hi = params["prompt_tokens"]["lo"], params["prompt_tokens"]["hi"]
    shared = params.get("shared_preamble_tokens", 0)
    assert all(lo <= n - shared - 1 <= hi for n in p1)


def test_a_kind_is_a_module_found_by_name_under_any_of_the_paths(
        tmp_path):
    """A later PR's kind is a new file in a directory of its own: the
    harness finds it by the name its mix gives, and edits nothing."""
    assert mf.kind_file(PATHS, bench_paths.REPO, "discussions") == \
        os.path.join(bench_paths.BENCH, "traffic", "kinds",
                     "discussions.py")
    kinds = tmp_path / "later" / "traffic" / "kinds"
    kinds.mkdir(parents=True)
    (kinds / "pings.py").write_text(
        "LOOP = 'open'\nasync def drive(run):\n"
        "    run.open_window(0.0)\n")
    found = mf.kind_file({"paths": ["benchmarks", "later"]},
                         str(tmp_path), "pings")
    assert loadgen.load_kind(found).LOOP == "open"
    for missing in ("nowhere", "../harness/loadgen"):
        with pytest.raises(SystemExit):
            mf.kind_file(PATHS, bench_paths.REPO, missing)


def test_discussion_transcript_grows_and_keeps_each_knights_prefix():
    params = mix("roundtable")
    spec = discussions.session_spec(params, 9, 0)
    assert spec["knights"] == ["Lancelot", "Galahad", "Percival"]
    t1 = spec["opening"]
    assert t1[0] == traffic.BOS_ID and len(t1) >= 1 + 512 + 256
    r1 = discussions.round_prompts(spec, t1, 1)
    answers = [[1000 + i] * 128 for i in range(3)]
    t2 = discussions.grow_transcript(spec, t1, 1, answers)
    r2 = discussions.round_prompts(spec, t2, 2)
    # Lancelot's round-two prompt starts with his round-one prompt and
    # his own answer: his slot is reused whole.
    own = r1[0][1] + answers[0]
    assert r2[0][1][:len(own)] == own
    # The others share the opening with him and diverge at their cue.
    assert r2[1][1][:len(t1)] == t1
    assert len(t2) == len(t1) + sum(
        len(discussions.cue_ids(k, 1)) + 128 for k in spec["knights"])
    # Two sessions of one seed share the preamble and nothing after it.
    other = discussions.session_spec(params, 9, 1)["opening"]
    assert other[:513] == t1[:513] and other[513:600] != t1[513:600]


@pytest.mark.parametrize("dist,lo,hi", [
    ({"dist": "fixed", "value": 128}, 128, 128),
    ({"dist": "uniform", "lo": 96, "hi": 160}, 96, 160),
    ({"dist": "bounded_pareto", "lo": 32, "hi": 512, "alpha": 1.3},
     32, 512)])
def test_sizes_are_the_distributions_own_quantiles(dist, lo, hi):
    sizes = traffic.population(dist, 64, 3, "x")
    assert min(sizes) >= lo and max(sizes) <= hi
    assert sorted(sizes) == sorted(traffic.population(dist, 64, 4, "x"))
    if dist["dist"] == "bounded_pareto":
        # heavy tail: the median sits far below the mean of the bounds
        assert sorted(sizes)[32] < (lo + hi) / 4
    if dist["dist"] == "uniform":
        assert abs(sum(sizes) / 64 - (lo + hi) / 2) < 1


def test_unknown_kinds_are_errors_not_defaults():
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "zipf"}, 0.5)
    with pytest.raises(SystemExit):
        mf.kind_file(PATHS, bench_paths.REPO, "grpc")
