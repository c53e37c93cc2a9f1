"""Toy-size configurations and a manifest for the CPU rehearsals: the same
adapter, generator, readers and harness as on the chip, at widths a test
run can hold. Nothing here is a benchmark configuration."""

import argparse

TOY_TRAIN = {
    "name": "toy-train", "adapter": "train_o2_dp",
    "vocab_size": 256, "n_positions": 128, "n_embd": 64, "n_layer": 2,
    "n_head": 4, "n_inner": 128, "multi_query": True, "layer_norm_epsilon": 1e-5,
    "engine": {"rows_per_chip": 2, "lr": 3e-4, "remat": False,
               "scan_layers": False, "check_steps": 3, "trace_steps": 2},
    "limits": {"loss_gap": 0.01, "first_gradient_norm_gap": 0.008,
               "first_gradient_projection_gap": 0.02, "moved_norm_gap": 0.3},
}
TOY_TRAIN_MIX = {"name": "toy-docs", "generator": "packed_docs",
                 "params": {"seq": 64, "doc_median": 20, "doc_sigma": 1.0,
                            "doc_min": 4, "doc_max": 64}}


def manifest():
    return {
        "workloads": [
            {"name": "toy-train-cell", "config": "toy-train", "traffic": "toy-docs", "chips": 1}],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s/chip"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "step_ms.train", "unit": "ms", "moves": "train_tokens_per_s"},
            {"name": "mfu_pct", "unit": "%", "moves": "train_tokens_per_s"},
            {"name": "device_idle_pct.train", "unit": "%", "moves": "train_tokens_per_s"}],
    }


def args(seed=5, seconds=1.5, trace=0):
    return argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
