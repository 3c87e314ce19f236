"""Least work of the level-1 sweep: the passes over the dataset that a
request mix needs, whatever implements them.

One sequential pass over an (n_pad, d) f32 dataset with its (n_pad,) f32
squared norms reads ``n_pad*d*4 + n_pad*4`` bytes.  Every row a tick's
requests sweep (query points, sample and ``prob_of`` sources) needs
``2*d`` flops per point.  All those rows are known when the tick starts,
so one pass could serve them all.
"""
from __future__ import annotations


def pass_bytes(n_pad: int, d: int) -> int:
    """Bytes of one pass over the dataset and its squared norms."""
    return n_pad * d * 4 + n_pad * 4


def tick_work(n_pad: int, d: int, rows: int) -> dict:
    """Least bytes and flops of one tick that sweeps ``rows`` rows in one
    pass."""
    return {"bytes": pass_bytes(n_pad, d), "flops": 2 * d * rows * n_pad}


def least_seconds(work: dict, peaks: dict) -> dict:
    """The least time of ``work`` on a chip with ``peaks`` and which of
    memory and compute bounds it."""
    t_mem = work["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = work["flops"] / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_mem, t_flop),
            "bound": "memory" if t_mem >= t_flop else "compute"}
