"""The benchmark's cells cut to a size the CPU runs in seconds: the same
files and code paths with smaller shards, a one-day scenario and three
clients a round; KWT-1 at
its published widths, the language model with fewer layers, narrower
widths and shorter sequences (still bfloat16, as on the chip)."""
from __future__ import annotations

import copy
import time

import jax

from chipbench import bench

CPU_PEAKS = {"cpu": {"bf16_flops": 1e12}}

LM = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
      "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512}


def small_cell(name: str, max_steps: int = 4) -> bench.Cell:
    cell = bench.find_cell(name)
    cfg = copy.deepcopy(cell.config)
    traffic = copy.deepcopy(cell.traffic)
    traffic["scenario"]["days"] = 1
    traffic["strategy"]["n"] = 3
    cfg["replay_updates"] = 2
    if "class" in cfg["program"]:
        cfg["data"].update(samples_per_client=[40, 80], n_test=64)
    else:
        cfg["model"].update(LM)
        cfg["program"]["replace"].update(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
            n_heads_padded=4, n_kv_heads_padded=2, d_ff=128, vocab=512,
            vocab_padded=512)
        cfg["data"].update(seq=16, n_test=4, doc_len_median=12)
    cfg["train"]["max_steps"] = max_steps
    cell.config, cell.traffic = cfg, traffic
    return cell


def run_small(name: str, seed: int = 5, seconds: float = 0.0,
              traced: bool = False, fault=None, control: bool = False,
              **kw) -> dict:
    """One run of a small cell on the CPU, through the harness's own
    ``run_cell``: everything a run does but the look for a chip."""
    cell = small_cell(name, **kw)
    return bench.run_cell(cell, seed, seconds, traced, jax.devices("cpu"),
                          t_start=time.perf_counter(), peaks=CPU_PEAKS,
                          fault=fault, control=control)
