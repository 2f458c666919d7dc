"""The harness finds a cell, a configuration, a traffic mix and a metric
by the names in ``BENCHMARK.json``: files a later change adds are used
without editing any file that exists. Works on a copy in a temporary
directory; the repository is left as it was."""
import json
import os
import shutil

from bench import harness
from bench.tests import tiny


def listing(root):
    return sorted(os.path.relpath(os.path.join(d, f), root)
                  for d, _, fs in os.walk(os.path.join(root, "bench"))
                  for f in fs if "__pycache__" not in d)


def test_added_files_are_found_by_name(tmp_path):
    before = listing(harness.ROOT)
    root = tiny.make_root(str(tmp_path))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "lm-tiny.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "lm-added"
    with open(os.path.join(b, "configs", "lm-added.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(b, "configs", "lm-tiny.py"),
                os.path.join(b, "configs", "lm-added.py"))
    with open(os.path.join(b, "traffic", "added-mix.json"), "w") as f:
        json.dump({"slots_per_chip": 3}, f)
    with open(os.path.join(b, "workloads", "lm.added.json"), "w") as f:
        json.dump({"config": "lm-added", "traffic": "added-mix",
                   "chips": 1, "sample_slots": 1,
                   "limits": {}}, f)
    with open(os.path.join(b, "metrics", "added_metric.py"), "w") as f:
        f.write("def read(ctx):\n    return 41.5\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["per_layer"].append({"name": "added_metric", "unit": "ms",
                            "better": "lower", "source": "host_clock",
                            "layer": "device", "moves": "setup_s",
                            "workloads": ["lm.added"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)

    cell = harness.Cell("lm.added", root)
    assert cell.config["name"] == "lm-added"
    assert cell.traffic == {"slots_per_chip": 3}
    assert cell.kind == "lm"
    assert [m["name"] for m in cell.metrics(trace=True)] == ["added_metric"]
    assert [m["name"] for m in cell.metrics(trace=False)] == ["setup_s"]
    assert cell.reader("added_metric").read({}) == 41.5
    assert cell.kind_module().build_objective is not None
    assert cell.reference().readings is not None
    assert listing(harness.ROOT) == before
