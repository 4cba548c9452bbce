"""The request plan: the seed changes the bits and nothing else."""
import json
import os

from bench import run
from bench.tests.helpers import BENCH


def _cell(name):
    return run.load_cell(name)


def test_two_seeds_plan_the_same_requests_but_for_their_seeds():
    for name in ("bigcrush-s16.one-gen", "smallcrush-s1.one-gen",
                 "bigcrush-s16-w4.one-gen"):
        cell = _cell(name)
        a = [run.request(cell.config, cell.traffic, 7, i) for i in range(50)]
        b = [run.request(cell.config, cell.traffic, 2 ** 40 + 3, i)
             for i in range(50)]
        strip = [{k: v for k, v in r.items() if k != "seeds"} for r in a]
        assert strip == [{k: v for k, v in r.items() if k != "seeds"}
                         for r in b]
        assert len({r["seeds"] for r in a}) == 50
        assert all(ra["seeds"] != rb["seeds"] for ra, rb in zip(a, b))


def test_lanes_get_seeds_of_their_own_and_nothing_else_changes():
    cell = _cell("smallcrush-s1.one-gen")
    traffic = dict(cell.traffic, generators=["splitmix64", "msweyl"])
    a = [run.request(cell.config, traffic, 7, i) for i in range(20)]
    b = [run.request(cell.config, traffic, 2 ** 40 + 3, i)
         for i in range(20)]
    assert [dict(r, seeds=None) for r in a] == [dict(r, seeds=None)
                                                for r in b]
    seeds = [s for r in a + b for s in r["seeds"]]
    assert len(set(seeds)) == len(seeds) == 80
    one = run.request(cell.config, cell.traffic, 7, 3)
    assert one["seeds"] == run.request_seed(7, 3)


def test_words_per_request_are_fixed_by_the_configuration():
    for name, words in (("bigcrush-s16.one-gen", 158918865),
                        ("smallcrush-s1.one-gen", 790528)):
        cell = _cell(name)
        table = run.reference.battery(cell.config["battery"],
                                      cell.config["scale"])
        assert sum(w for _, _, w in table) == words
        assert cell.config["words_per_generator"] == words
        assert len(table) == cell.config["tests"]


def test_request_seeds_fit_the_programs_int32_for_any_run_seed():
    for seed in (0, 1, 2 ** 31 - 1, 2 ** 31, 2 ** 33 + 5, 10 ** 18):
        for i in range(20):
            s = run.request_seed(seed, i)
            assert 0 <= s < 2 ** 31
            assert s == run.request_seed(seed, i)


def test_every_cell_names_files_that_exist():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "configs",
                                           w["config"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           run.quantity(m["name"]) + ".py"))
