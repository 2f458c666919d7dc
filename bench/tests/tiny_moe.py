"""A copy of the benchmark's files with an MoE cell small enough for the
CPU: ``lm.moe.tiny``, the ``lm_moe`` kind (latent attention, a leading
dense layer, sigmoid-routed experts with a held share and shared experts)
at narrow widths and a short sequence, holding experts 4-7 of 16. The copy
lives in a directory the caller gives, so the repository's own files are
never touched."""
from __future__ import annotations

import json
import os

from bench.tests import tiny

# Set from CPU readings of this tiny cell over 8 seeds: the program reads
# at most 7.6e-7 (init), 2.5e-2 (grad1), 7.4e-2 (dparam), 1.9e-3 (loss);
# the control at least 1.2e-3 (init), 1.0 (dparam); half the batch at
# least 0.12 (grad1), 0.034 (loss).
LIMITS = {"init_gap": 1e-5, "grad1_gap": 0.06, "dparam_gap": 0.3,
          "loss_gap": 0.01, "slot_update_mismatch": 0, "report_mismatch": 0,
          "status_mismatch": 0}

CELL = "lm.moe.tiny"


def _write(root, rel, obj):
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp: str) -> str:
    """``tiny.make_root``'s copy, with the MoE cell added to it."""
    root = tiny.make_root(tmp)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "moonlight-16b-a3b-1d4m.json")) as f:
        moe = json.load(f)
    moe.update(name="lm-moe-tiny", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               moe_intermediate_size=32, n_routed_experts=4, expert_offset=4,
               num_hidden_layers=3, vocab_size=256)
    moe["published"] = dict(moe["published"], n_routed_experts=16)
    _write(root, "bench/configs/lm-moe-tiny.json", moe)
    with open(os.path.join(b, "configs", "moonlight-16b-a3b-1d4m.py")) as f:
        ref = f.read()
    with open(os.path.join(b, "configs", "lm-moe-tiny.py"), "w") as f:
        f.write(ref)
    with open(os.path.join(b, "traffic", "lm-ht1-8k.json")) as f:
        tr = json.load(f)
    tr.update(slots_per_chip=2, seq=32, episodes_per_phase=3,
              max_updates=3, phases=2)
    _write(root, "bench/traffic/lm-moe-tiny.json", tr)
    _write(root, f"bench/workloads/{CELL}.json", {
        "config": "lm-moe-tiny", "traffic": "lm-moe-tiny", "chips": 1,
        "sample_slots": 2, "limits": LIMITS})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for group in ("end_to_end", "per_layer"):
        for m in bm[group]:
            if "workloads" in m:
                m["workloads"] = [CELL if w == "lm.moonlight.ht1" else w
                                  for w in m["workloads"]]
    _write(root, "BENCHMARK.json", bm)
    return root
