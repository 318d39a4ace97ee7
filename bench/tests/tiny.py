"""The benchmark's cells cut to widths a CPU test can run: every key that
the harness reads is the cell's own except the sizes below."""
import dataclasses

from bench.run import load_cell

TINY_MODEL = dict(num_layers=2, d_model=64, vocab_size=256, head_dim=16,
                  ssm_state_dim=16, ssm_head_dim=16, ssm_chunk=16)
TINY_TRAFFIC = dict(seq_len=64, trace_steps=3)
#: the smallest cut at which the fp8 control's gradient error already
#: exceeds the cells' `grad_err` limits (read at the cells' own sizes on
#: the chip); at TINY_MODEL it reads about half of that
CONTROL_MODEL = dict(num_layers=8, d_model=256, vocab_size=2048,
                     head_dim=64, ssm_state_dim=64, ssm_head_dim=64,
                     ssm_chunk=128)
CONTROL_TRAFFIC = dict(seq_len=512)
CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(name: str, model=TINY_MODEL, traffic=TINY_TRAFFIC):
    cell = load_cell(name)
    config = dict(cell.config, model=dict(cell.config["model"], **model))
    return dataclasses.replace(
        cell, config=config, traffic=dict(cell.traffic, **traffic))
