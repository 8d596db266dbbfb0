"""Training tokens per second over one whole refresh period.

A period of the job is one refresh step and ``period - 1`` steady steps, so
its time is ``T_refresh + (period - 1) * T_steady``, each the total wall time
of the measured steps of its kind over their count.
"""
from __future__ import annotations


def is_refresh(step: int, period: int) -> bool:
    return period > 0 and step % period == 0


def period_seconds(refresh_times, steady_times, period: int) -> float:
    if not refresh_times or not steady_times:
        raise ValueError("need at least one refresh and one steady step")
    t_refresh = sum(refresh_times) / len(refresh_times)
    t_steady = sum(steady_times) / len(steady_times)
    return t_refresh + (period - 1) * t_steady


def tokens_per_s(refresh_times, steady_times, period: int,
                 tokens_per_step: int) -> float:
    return period * tokens_per_step / period_seconds(
        refresh_times, steady_times, period)
