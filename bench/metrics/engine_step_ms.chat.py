"""Mean host-clock ``ServeEngine.step`` over the traced window, in ms: the
engine's own ``serve/step_s`` histogram (each step ends in a host sync on
its tokens)."""


def read(tw):
    return None if tw.engine_step_s is None else tw.engine_step_s * 1e3
