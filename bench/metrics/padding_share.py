"""padding_share: bucket padding rows over all rows dispatched to the
backbone, in percent."""


def read(run):
    dispatched = sum(t.rows + t.padding for t in run.ticks)
    if not dispatched:
        return None
    return 100.0 * sum(t.padding for t in run.ticks) / dispatched
