"""Mean device time of the decode-step module (``jit_decode_step``) over
the steps in the traced window, in ms, from the profiler's module line."""


def read(tw):
    runs = tw.module_s("decode_step")
    return sum(runs) / len(runs) * 1e3 if runs else None
