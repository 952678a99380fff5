"""``step_ms_p90`` read over spans of 250 ms or more (as many steps as the
window's median period needs for that, one span starting at every
completion): the host's clock is off by some half a millisecond, which a
single step of a fast cell feels and such a span does not. The steadier
figure beside the end-to-end one."""
LAYER = "train step"
UNIT = "ms"
MOVES = "step_ms_p90"


def read(window):
    from benchmark.loops import train

    return train.period_p90(window.stamps, train.smooth_steps(window.stamps))
