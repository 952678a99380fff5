"""Tiny cells for the benchmark's CPU tests: the real configuration and
traffic files cut to sizes a test run can hold (the chip cells keep the
published widths; these exist only here)."""
import copy
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

# limits for these sizes, set as the chip cells' are: grad_rel_err reads at
# most 0.0135 for the program and at least 0.035 for the fp8 control over
# five seeds of each family (CPU, PR 23); the rest sit three times over the
# program's largest
LIMITS = {"loss_gap_1": 3e-4, "loss_gap_2": 5e-4, "loss_gap_3": 5e-4,
          "grad_norm_gap": 0.3, "grad_rel_err": 0.022, "delta_norm_gap": 0.3}


def cell(family, mesh=None):
    if family == "gpt2":
        cfg = harness.load_json("configs", "gpt2-small.json")
        cfg.update(n_layer=2, n_embd=64, n_head=2, n_positions=64,
                   vocab_size=512)
        traffic = harness.load_json("traffic", "packed_lm_1k_b16.json")
        traffic.update(batch=8, seq_len=64, pool_batches=4, eos_token=511)
        traffic["documents"]["median_len"] = 20
    else:
        cfg = harness.load_json("configs", "bert-base-uncased.json")
        cfg.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=2,
                   intermediate_size=128, max_position_embeddings=64,
                   vocab_size=2000)
        traffic = harness.load_json("traffic", "bert_mlm_512_b24.json")
        traffic.update(batch=8, seq_len=64, pool_batches=5,
                       max_predictions_per_seq=10)
    return {"name": "tiny_" + family, "chips": 4 if mesh else 1,
            "loop": "train", "mesh": mesh,
            "limits": copy.deepcopy(LIMITS), "config": cfg,
            "traffic": traffic}


def run_args(seed, seconds=0.3):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
