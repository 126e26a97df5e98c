"""Readings of the traced window that more than one metric reports, one
per cell whose end-to-end metric it moves (``bench/metrics/<name>.py``
binds its ``read`` to one of these).  Each returns None where the run was
not traced or stepped nothing in the trace."""
from __future__ import annotations


def step_ms(run):
    """Device busy time in the traced window over the engine steps run in
    it, in milliseconds (the trace starts and stops at burst boundaries,
    where the device has finished every step dispatched)."""
    t = run.trace
    if t is None or not t.steps:
        return None
    return t.busy_s / t.steps * 1e3


def mfu(run):
    """Model FLOPs of the tokens the traced window's steps processed
    (prompt and output tokens of live slots, from shapes: bench/counts.py)
    over the window's length times the chip's bf16 peak, in percent."""
    t = run.trace
    if t is None or not t.steps:
        return None
    return t.flops / (t.window_s * run.peaks["bf16_flops_per_s"]) * 100.0


def step_roofline(run):
    """The least time the traced window's steps need at the chip's peaks
    (per compiled burst, the larger of FLOPs over peak FLOP/s and needed
    bytes over HBM bandwidth: bench/counts.py) over the device time of the
    burst programs, in percent.  The fused step as a whole stands for its
    kernels until the program names them in the trace."""
    t = run.trace
    if t is None or not t.steps:
        return None
    device_s = t.burst_s if t.burst_s else t.busy_s
    return t.bound_s / device_s * 100.0


def device_idle_share(run):
    """One minus the union of device operation intervals over the traced
    window's length, in percent."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return (1.0 - t.busy_s / t.window_s) * 100.0
