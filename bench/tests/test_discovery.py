"""The harness finds a cell, a configuration, a traffic mix and a metric
by the names in ``BENCHMARK.json``: files a later change adds are used
without editing any file that exists. Works on a copy in a temporary
directory; the repository is left as it was."""
import json
import os
import shutil

import pytest

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


# --- a second LM kind, made of new files only -------------------------------
KIND = '''"""An LM kind that states its head width in the file."""
import dataclasses
import os

from bench import harness

lm = harness.load_module(os.path.join(os.path.dirname(__file__), "lm.py"),
                         "bench_kind_lm")
engine_kwargs, params, grad_moment, counter, loss_sum = (
    lm.engine_kwargs, lm.params, lm.grad_moment, lm.counter, lm.loss_sum)


def model_config(config):
    from repro.configs.registry import get_config
    return dataclasses.replace(
        get_config(config["program_arch"]),
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], rope_theta=config["rope_theta"],
        dtype=config["torch_dtype"])


def build_objective(config, traffic):
    return lm.objective(model_config(config), config, traffic)
'''

FLOPS = '''"""Operations and bytes of the added kind, by hand."""


def flops_per_token(cfg, seq):
    return 6.0 * cfg["hidden_size"] ** 2 + 2.0 * cfg["hidden_size"] * seq


def update_bytes(cfg):
    return 20.0 * cfg["hidden_size"] ** 2


def attention_work(cfg, batch, seq):
    return (6.0 * cfg["hidden_size"] * seq * seq * batch,
            24.0 * cfg["hidden_size"] * seq * batch)
'''

READ_BY_ALL = ["tokens_per_s", "peak_hbm_gib", "mfu.lm", "step_roofline.lm",
               "device_idle.lm", "attention_roofline.lm"]


@pytest.fixture(scope="module")
def added_kind(tmp_path_factory):
    """A copy with kind ``lm_hd``: its kind and flops modules, a
    configuration, its reference and a cell, all new files, and the cell
    appended to the workloads of the LM metrics."""
    before = listing(harness.ROOT)
    root = tiny.make_root(str(tmp_path_factory.mktemp("kind")))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "kinds", "lm_hd.py"), "w") as f:
        f.write(KIND)
    with open(os.path.join(b, "flops", "lm_hd.py"), "w") as f:
        f.write(FLOPS)
    with open(os.path.join(b, "configs", "lm-tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(name="lm-hd", kind="lm_hd", head_dim=16)
    with open(os.path.join(b, "configs", "lm-hd.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(b, "configs", "lm-tiny.py"),
                os.path.join(b, "configs", "lm-hd.py"))
    with open(os.path.join(b, "workloads", "lm.tiny.json")) as f:
        cell = json.load(f)
    cell["config"] = "lm-hd"
    with open(os.path.join(b, "workloads", "lm.hd.json"), "w") as f:
        json.dump(cell, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in READ_BY_ALL:
            m["workloads"].append("lm.hd")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    yield root
    assert listing(harness.ROOT) == before


def test_a_kind_of_new_files_gets_the_lm_metrics(added_kind):
    cell = harness.Cell("lm.hd", added_kind)
    assert cell.kind == "lm_hd"
    assert [m["name"] for m in cell.metrics(trace=False)] == [
        "tokens_per_s", "peak_hbm_gib", "setup_s"]
    assert sorted(m["name"] for m in cell.metrics(trace=True)) == sorted(
        ["mfu.lm", "step_roofline.lm", "device_idle.lm",
         "attention_roofline.lm"])
    # the kind reuses the very module the harness holds for kind lm
    kind = cell.kind_module()
    assert kind.lm is harness.Cell("lm.tiny", added_kind).kind_module()
    obj = kind.build_objective(cell.config, cell.traffic)
    assert obj.cfg.head_dim == 16 and obj.cfg.n_layers == 1
    assert obj.table.shape == (cell.config["vocab_size"], 8)
    assert obj.cache_key()[1] is obj.cfg

    reduced = {"window_s": 3.0, "busy_s": 2.7, "devices": 1,
               "step_calls": 10, "step_s": 0.5, "gaps": [],
               "ops": [("%fusion.3 = bf16[4,64]", 0.3)],
               "step_ops": [("%fusion.3 = bf16[4,64]", 0.3),
                            ("%flash_attention.2 = bf16[2,1,4,32,16]", 0.02),
                            ("%flash_mha_bwd_dq_block_q_major_32.1 = bf16",
                             0.04)]}
    ctx = {"cell": cell, "chips": 1, "flops": cell.flops(),
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "setup_s": 12.5, "window_s": 1.0, "work_per_s": 640.0,
           "peak_bytes": 2 ** 29, "trace": reduced,
           "trace_work_per_s": 512.0}
    values = {m["name"]: cell.reader(m["name"]).read(ctx)
              for m in cell.metrics(False) + cell.metrics(True)}
    d, seq = 64, 32
    work = 2 * 32                          # 2 slots of 1 x 32 tokens
    per_token = 6.0 * d * d + 2.0 * d * seq
    assert values == pytest.approx({
        "tokens_per_s": 640.0, "peak_hbm_gib": 0.5, "setup_s": 12.5,
        "device_idle.lm": 10.0,
        "mfu.lm": 100 * per_token * 512.0 / 1e12,
        "step_roofline.lm": 100 * max(work * per_token / 1e12,
                                      2 * 20.0 * d * d / 1e11) / 0.05,
        "attention_roofline.lm": 100 * max(6.0 * d * seq * seq * 2 / 1e12,
                                           24.0 * d * seq * 2 / 1e11)
        / 0.006})


def test_a_kind_of_new_files_runs_correct(added_kind):
    """A whole run of the added kind's cell on the CPU: the program's
    objective built by the new kind module, correct against the plain
    reference, with its end-to-end metrics."""
    res = harness.run_cell("lm.hd", 2 ** 31 + 21, 0.5, False,
                           root=added_kind, platform="cpu", log=lambda s: 0)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                   "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0
