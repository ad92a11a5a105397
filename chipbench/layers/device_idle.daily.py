"""Device layer (TPU): percent of the traced window in which no
operation ran on the chip, 100 * (1 - busy_s / window_s)."""

from chipbench.readers import device_idle_pct


def read(run):
    return device_idle_pct(run)
