"""output_tok_s: output tokens that became visible in the window, over
the window's length."""


def read(run):
    n = sum(1 for t in run.tracked for s in t.stamps if run.in_window(s))
    return n / run.seconds
