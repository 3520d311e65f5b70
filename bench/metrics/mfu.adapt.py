"""Whole adaptation step's share of the chip's bf16 peak: the operations
Algorithm 1 needs for each task of the window (probe and sparse steps, from
shapes and the task's own policy, ``bench/flops.py``) over the window's
seconds."""


def read(r):
    if not r.get("flops") or r.get("peaks") is None:
        return None
    return 100.0 * r["flops"] / r["window_s"] / r["peaks"]["bf16_flops"]
