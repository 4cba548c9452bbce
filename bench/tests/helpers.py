"""A small benchmark tree for the tests: ``BENCHMARK.json``, one
configuration, the real traffic and metric readers, and the program's
sources, under a temporary root."""
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY = {"name": "tiny", "battery": "smallcrush", "scale": 0.0625,
        "n_workers": 1, "tests": 10, "words_per_generator": 55552,
        "backend": "auto", "policy": "lpt", "alpha": 0.01,
        "verdict_engine": "bonferroni", "stop_on_verdict": False,
        "limits": {"stat_gap": 1e-3, "p_gap": 1e-2}}


def tiny_tree(tmp, n_workers=1, traffic="one-gen", traffic_body=None):
    """Root and bench directory of a one-cell benchmark ``tiny.<traffic>``
    whose configuration is SmallCrush at scale 1/16 on ``n_workers``."""
    root = os.path.join(str(tmp), "root")
    bench = os.path.join(root, "bench")
    os.makedirs(os.path.join(bench, "configs"))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bench, "metrics"))
    shutil.copytree(os.path.join(BENCH, "traffic"),
                    os.path.join(bench, "traffic"))
    shutil.copytree(os.path.join(BENCH, "drivers"),
                    os.path.join(bench, "drivers"))
    if traffic_body is not None:
        with open(os.path.join(bench, "traffic", traffic + ".json"), "w") as f:
            json.dump(traffic_body, f)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump(dict(TINY, n_workers=n_workers), f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = f"tiny.{traffic}"
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": cell, "config": "tiny",
                          "traffic": traffic, "chips": n_workers,
                          "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cell]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root, bench, cell
