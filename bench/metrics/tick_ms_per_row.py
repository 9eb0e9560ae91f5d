"""tick_ms_per_row: device milliseconds of the tick programs (XLA modules
named jit_tick: gather, backbone, scatter, policy step, DDIM) in the
traced window, over the backbone rows they computed."""

TICK_MODULE = "jit_tick"


def read(run):
    rows = sum(t.rows for t in run.ticks)
    if run.trace is None or not rows:
        return None
    dev = sum(s for name, s in run.trace.module_s.items()
              if name == TICK_MODULE)
    if not dev:
        return None
    return 1000.0 * dev / rows
