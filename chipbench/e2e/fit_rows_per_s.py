"""Rows fitted per second: n * (1 + B) for each fit with CIs completed,
over the time from the window's start to the end of its last fit."""

from chipbench.readers import rate


def read(run):
    return rate(run, "rows")
