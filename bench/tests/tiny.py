"""Tiny versions of the benchmark's cells, for the CPU: the same harness
paths at a size a test can hold (128 x 128 tiles on a 512 x 1024 FFN, so
rho 0.5 and 0.75 stay exact)."""
from __future__ import annotations

import argparse
import copy

from bench.harness import lookup

TINY_MODEL = {
    "name": "tiny", "n_layers": 2, "d_model": 512, "n_heads": 4,
    "n_kv_heads": 2, "head_dim": 128, "d_ff": 1024, "vocab_size": 512,
    "max_seq_len": 1024, "qkv_bias": True, "rope_theta": 10000.0,
    "rms_eps": 1e-6, "act": "silu", "ffn_gated": True,
    "tie_embeddings": False, "dtype": "bfloat16", "param_dtype": "bfloat16",
    "attn_chunk": 64, "loss_chunk": 64,
    "sparsity": {"enabled": True, "rho_ffn": [0.5, 0.75], "block_in": 128,
                 "block_out": 128, "method": "clashfree", "cf_type": 1,
                 "dither": False, "seed": 0, "backend": "auto"},
}

TINY_MOE = dict(TINY_MODEL, name="tiny-moe", n_layers=2, d_ff=1024,
                qkv_bias=False, tie_embeddings=True, param_dtype="float32",
                moe={"n_routed": 4, "top_k": 2, "n_shared": 0,
                     "d_expert": 1024, "capacity_factor": 1.25,
                     "router_zloss": 0.001, "first_layer_dense": False,
                     "dense_d_ff": 0},
                sparsity=dict(TINY_MODEL["sparsity"], moe_sparsity=True))

# each junction's pattern seed offset (the program's FFN and MoE layers)
SEEDS = {False: {"up": 12, "gate": 13, "down": 14},
         True: {"up": 32, "gate": 33, "down": 34}}

SERVE_CELL = {
    "engine": {"max_slots": 4, "page_size": 16, "total_pages": 64,
               "max_pages_per_seq": 16, "token_budget": 36,
               "prefill_chunk": 32, "greedy": True},
    "rate_per_s": 4.0, "trace_seconds": 1.0, "drain_cap_s": 60,
    "correct": {"served_logit_gap": 0.5, "sample_tokens": 32,
                "sample_requests": 3},
}

CHAT = {"kind": "serve", "arrival": "poisson",
        "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 1.0,
                       "min": 4, "max": 200},
        "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                       "min": 2, "max": 40}}

BACKLOG = {"kind": "serve", "arrival": "backlog", "block": 16,
           "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                          "min": 4, "max": 64},
           "output_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                          "min": 4, "max": 120}}

TRAIN = {"kind": "train", "batch": 2, "seq": 64, "pool": 4,
         "branching": 8, "noise": 0.05}

TRAIN_CELL = {"opt": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                      "weight_decay": 0.1, "grad_clip": 1.0,
                      "warmup_steps": 1, "total_steps": 1000000,
                      "min_lr_ratio": 1.0, "schedule": "constant"},
              "trace_steps": 2,
              # set as the cells' limits are, from this size's readings
              # on the CPU: sound 0.0021 / 0.0136 / 0.00068; control
              # (bf16 master weights) change 0.083; half batch 0.0237 /
              # 0.061 / 0.0138
              "correct": {"loss_gap": 0.008, "grad1_leaf_gap": 0.05,
                          "change3_leaf_gap": 0.005}}


def cell(kind: str) -> lookup.Cell:
    """A tiny cell of ``kind``: ``chat``, ``backlog`` or ``train``."""
    if kind == "train":
        model, traffic, cc, ref = TINY_MOE, TRAIN, TRAIN_CELL, "tiny_moe"
    else:
        model = TINY_MODEL
        traffic = CHAT if kind == "chat" else BACKLOG
        cc, ref = SERVE_CELL, "qwen2_7b"
    c = lookup.Cell(
        name=f"tiny.{kind}", chips=1, config_name=ref,
        traffic_name=kind,
        config={"model": copy.deepcopy(model),
                "junction_seeds": SEEDS[kind == "train"],
                "record": {"params_dense": None}},
        traffic=copy.deepcopy(traffic), cell=copy.deepcopy(cc),
        end_to_end=[], per_layer=[])
    if kind == "train":
        c.config_name = "granite_moe_1b_a400m"
    return c


def args(seed=1234567891234, seconds=2.0, trace=0):
    return argparse.Namespace(workload="tiny", seed=seed, seconds=seconds,
                              trace=trace)
