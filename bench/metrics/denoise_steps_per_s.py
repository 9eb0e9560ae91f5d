"""denoise_steps_per_s: denoising steps advanced in the window (one per
busy slot per tick, computed or served from the cache) over the window's
seconds, from its opening to the end of its last tick."""


def read(run):
    return run.steps_advanced / run.window_s
