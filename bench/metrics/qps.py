"""qps (queries/s): queries answered correctly within the window,
over the window's seconds (a request of the pre-roll that is answered
in the window counts: the window sees the loop's steady throughput)."""


def read(run):
    w0, w1 = run.window
    good = sum(ok and w0 <= r.done <= w1
               for r, ok in zip(run.requests, run.answered_ok))
    return good / run.window_s
