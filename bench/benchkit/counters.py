"""Arithmetic on the device program's own counters.

A sweep runs one program per shape bucket; each program advances its
batch of ``B`` cells in one ``while`` loop that runs until its slowest
cell is done. ``programs`` rows are ``(B, max_iters, sum_iters)``: the
batch size, the trips the loop ran (the largest cell ``n_iters``) and
the trips the cells needed (their sum).
"""
from __future__ import annotations


def device_trips(programs) -> int:
    """Trips the device ran: one per loop trip of each program."""
    return sum(mx for _, mx, _ in programs)


def trip_waste(programs):
    """Share of the batch's trips that advanced a cell already done:
    1 - needed / (B x trips ran). None for no trips."""
    ran = sum(b * mx for b, mx, _ in programs)
    if not ran:
        return None
    return 1.0 - sum(s for _, _, s in programs) / ran
