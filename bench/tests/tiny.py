"""A copy of the benchmark's files with a cell small enough for the CPU:
``lm.tiny``, the LM cell at a narrow width and a short sequence. The copy
lives in a directory the caller gives, so the repository's own files are
never touched."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Set from CPU readings of this tiny cell over 9 seeds and hash seeds:
# the program reads at most 1.7e-3 (grad1), 3.1e-2 (dparam), 3e-4
# (loss), 0 (init); the control at least 1.1e-3 (init), 1.0 (dparam);
# half the batch at least 0.15 (grad1), 0.029 (loss).
LIMITS = {"init_gap": 1e-5, "grad1_gap": 0.02, "dparam_gap": 0.3,
          "loss_gap": 0.01, "slot_update_mismatch": 0, "report_mismatch": 0,
          "status_mismatch": 0}


def _write(root, rel, obj):
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp: str) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "phi3-mini-3.8b-1L.json")) as f:
        lm = json.load(f)
    lm.update(name="lm-tiny", hidden_size=64, intermediate_size=128,
              num_attention_heads=4, num_key_value_heads=4, vocab_size=256)
    _write(root, "bench/configs/lm-tiny.json", lm)
    shutil.copy(os.path.join(b, "configs", "phi3-mini-3.8b-1L.py"),
                os.path.join(b, "configs", "lm-tiny.py"))
    with open(os.path.join(b, "traffic", "lm-ht2.json")) as f:
        tr = json.load(f)
    tr.update(seq=32, episodes_per_phase=3, max_updates=3, phases=2)
    _write(root, "bench/traffic/lm-tiny.json", tr)
    _write(root, "bench/workloads/lm.tiny.json", {
        "config": "lm-tiny", "traffic": "lm-tiny", "chips": 1,
        "sample_slots": 2, "limits": LIMITS})
    with open(os.path.join(b, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    _write(root, "bench/peaks.json", peaks)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bm = json.load(f)
    cells = {"lm.phi3.ht2": "lm.tiny"}
    for group in ("end_to_end", "per_layer"):
        for m in bm[group]:
            if "workloads" in m:
                m["workloads"] = [cells.get(w, w) for w in m["workloads"]]
    _write(root, "BENCHMARK.json", bm)
    return root
