"""Kernels: the time one chip's HBM peak needs for its share of the bytes a
window must move (``peaks.window_bytes``, from the configuration's shapes
alone; the table and the window's planes are split evenly over the chips on
the shard axis) over the device time a window took on a chip (the mean over
the chips), in percent."""

from chipbench import peaks


def read(ctx):
    if not ctx["windows"] or ctx["trace"]["busy_s"] <= 0:
        return None
    chip_bytes = peaks.window_bytes(ctx["config"]) / ctx["trace"]["n_devices"]
    least_s = chip_bytes / peaks.hbm_peak(ctx["device_kind"])
    return least_s / (ctx["trace"]["busy_s"] / ctx["windows"]) * 100.0
