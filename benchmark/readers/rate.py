"""Reader ``rate``: statements answered over the whole window, per second
of the window (first statement released to last answer received), on the
clients' clock."""


def read(run):
    seconds = run.window_end - run.window_start
    if not run.answered or seconds <= 0:
        return None
    return len(run.answered) / seconds
