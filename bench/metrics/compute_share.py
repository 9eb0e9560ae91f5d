"""compute_share: backbone rows computed over branch-steps due (each busy
slot's cond branch plus each busy guided slot's uncond branch, per tick),
in percent.  100 means no cache reuse."""


def read(run):
    due = sum(t.active + t.guided for t in run.ticks)
    if not due:
        return None
    return 100.0 * sum(t.rows for t in run.ticks) / due
