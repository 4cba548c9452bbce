"""A cell's traffic, configuration and metric readers are found by name:
adding one is adding a file and an entry."""
import json
import os

import pytest

from bench import run
from bench.tests.helpers import tiny_tree


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    body = {"driver": "closed_loop",
            "generators": ["splitmix64", "msweyl"], "why": "test"}
    root, bench, cell = tiny_tree(tmp_path, traffic="dummy",
                                  traffic_body=body)
    found = run.load_cell(cell, bench, root)
    assert found.traffic == body
    assert found.config["name"] == "tiny"
    assert found.chips == 1
    reqs = [run.request(found.config, found.traffic, 3, i) for i in range(3)]
    assert all(r["generators"] == ("splitmix64", "msweyl") for r in reqs)
    assert all(len(set(r["seeds"])) == 2 for r in reqs)


@pytest.mark.parametrize("body, why", [
    ({"driver": "closed_loop", "generators": ["splitmix64"],
      "loop": "open"}, "does not read"),
    ({"driver": "closed_loop", "generators": ["splitmix64"],
      "clients": 4}, "does not read"),
    ({"driver": "closed_loop", "generators": []}, "generator"),
    ({"driver": "closed_loop", "generators": ["randu", "randu"]},
     "generator"),
    ({"driver": "closed_loop", "generators": ["randu"]}, "no plain reference"),
    ({"generators": ["splitmix64"]}, "names no driver"),
    ({"driver": "open_loop", "generators": ["splitmix64"]}, "no driver"),
    ({"driver": "../run", "generators": ["splitmix64"]}, "not a name"),
])
def test_traffic_the_driver_does_not_read_is_refused(tmp_path, body, why):
    root, bench, cell = tiny_tree(tmp_path, traffic="bad",
                                  traffic_body=body)
    with pytest.raises((ValueError, FileNotFoundError), match=why):
        run.load_cell(cell, bench, root)


def test_a_new_driver_is_found_by_name(tmp_path):
    """A kind of traffic the closed loop cannot express is a driver file
    of its own: here one that submits a single request and polls it to
    the end, whatever the deadline."""
    body = {"driver": "one_shot", "generators": ["splitmix64"]}
    root, bench, cell = tiny_tree(tmp_path, traffic="once",
                                  traffic_body=body)
    with open(os.path.join(bench, "drivers", "one_shot.py"), "w") as f:
        f.write("def validate(traffic):\n"
                "    assert set(traffic) == {'driver', 'generators'}\n"
                "\n\n"
                "def drive(w):\n"
                "    r = w.submit()\n"
                "    while r.pending:\n"
                "        w.poll(r)\n"
                "    w.finish(r)\n")
    found = run.load_cell(cell, bench, root)
    assert found.driver.__name__ == "bench_drivers_one_shot"
    out = run.run_cell(cell, 5, 0.0, False, bench, root,
                       need_accelerator=False, log=lambda m: None)
    assert out["attempted"] == 1 and out["correct"] is True, out["checks"]


def test_a_new_metric_reader_is_found_by_name(tmp_path):
    root, bench, cell = tiny_tree(tmp_path)
    with open(os.path.join(bench, "metrics", "answer_ms.py"), "w") as f:
        f.write("def read(ctx):\n    return 42.0 if ctx else None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["per_layer"].append({"name": "answer_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "stitch and verdict", "moves": "words_per_s",
                              "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    found = run.load_cell(cell, bench, root)
    assert "answer_ms" in [m["name"] for m in found.per_layer]
    assert run.load_reader("answer_ms", bench)(object()) == 42.0
    assert run.load_reader("answer_ms", bench)(None) is None


def test_metrics_that_list_other_cells_do_not_apply():
    cell = run.load_cell("smallcrush-s1.one-gen")
    names = [m["name"] for m in cell.end_to_end]
    assert "verdict_p95_s" in names and "words_per_s.smallcrush" in names
    assert "words_per_s" not in names
    assert "straggler_ms_per_round" not in [m["name"] for m in cell.per_layer]
    big = run.load_cell("bigcrush-s16.one-gen")
    assert "verdict_p95_s" not in [m["name"] for m in big.end_to_end]
    w4 = run.load_cell("bigcrush-s16-w4.one-gen")
    assert "straggler_ms_per_round" in [m["name"] for m in w4.per_layer]


def test_a_metric_of_a_group_of_cells_reads_its_quantity():
    assert run.quantity("words_per_s.smallcrush") == "words_per_s"
    assert run.quantity("setup_s") == "setup_s"
    assert (run.load_reader("device_idle_share.smallcrush").__module__
            == run.load_reader("device_idle_share").__module__)


def test_a_generator_reference_is_found_by_name():
    from bench import reference
    assert {"splitmix64", "msweyl"} <= set(reference.generators())
    a = reference.generator("msweyl")(7, 3, 1000)
    b = reference.generator("splitmix64")(7, 3, 1000)
    assert a.dtype == b.dtype and a.shape == b.shape == (1000,)
    assert (a != b).mean() > 0.99
    with pytest.raises(KeyError, match="no plain reference"):
        reference.generator("randu")
