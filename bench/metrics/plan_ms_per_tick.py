"""plan_ms_per_tick: mean host milliseconds per tick the engine spent
deciding the tick (the fused want pass and its device_get), from
TickEvent.plan_seconds."""


def read(run):
    if not run.ticks:
        return None
    return 1000.0 * sum(t.plan_s for t in run.ticks) / len(run.ticks)
