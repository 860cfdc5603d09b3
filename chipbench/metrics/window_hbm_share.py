"""Kernels: the time the chip's HBM peak needs for the bytes a window must
move (``peaks.window_bytes``, from the configuration's shapes alone) over
the device time a window took, in percent."""

from chipbench import peaks


def read(ctx):
    if not ctx["windows"] or ctx["trace"]["busy_s"] <= 0:
        return None
    least_s = peaks.window_bytes(ctx["config"]) / peaks.hbm_peak(ctx["device_kind"])
    return least_s / (ctx["trace"]["busy_s"] / ctx["windows"]) * 100.0
