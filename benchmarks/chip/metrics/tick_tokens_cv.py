"""Scheduler + Token Throttling: coefficient of variation of the prefill
and decode tokens scheduled per non-empty tick in the traced sub-window
(`SchedulerStats`)."""

from stats import cv


def read(ctx):
    return cv(ctx["sub"]["tick_tokens"])
