"""The whole decode step's share of the chip's peak, in %: the least time
the step's needed work could take (the larger of its FLOPs at the bf16
peak and its bytes at HBM bandwidth, from ``bench/arch/<kind>.py`` and the
live positions of the active lanes) over the measured device time of the
step module, both as means over the traced window's steps."""


def read(tw):
    runs = tw.module_s("decode_step")
    least = tw.least_step_s()
    if not runs or least is None:
        return None
    return least[0] / (sum(runs) / len(runs)) * 100.0
