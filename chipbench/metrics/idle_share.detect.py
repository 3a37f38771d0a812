"""Share of the traced detection window in which no op ran on the chip, in %."""


def read(run):
    return 100.0 * (1.0 - run.fold.busy_s / run.fold.window_s)
