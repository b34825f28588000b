"""Requests completed in the window over the window's seconds, on the
harness's clock; the drains between the driver's calls count."""


def read(run):
    span = run.t_end - run.t_start
    done = sum(1 for d in run.done if d.ok)
    return done / span if span > 0 and done else None
