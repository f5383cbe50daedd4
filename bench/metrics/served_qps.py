"""Answers completed inside the window, per second of the window.
(A wrong answer makes the run incorrect, whatever this reads.)"""


def read(run):
    done = [r for r in run.window_requests()
            if r.answered and r.done <= run.t1]
    return len(done) / run.seconds if done else None
