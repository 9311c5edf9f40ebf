"""Input events whose results reached the callback inside the window (the
configuration's reference says which: `completed`), over the window's
seconds. Counted on the client's side, on the callback's clock, not from
`drain()`."""


def read(run: dict):
    done = run["reference"].completed(run, run["t0_ns"], run["t_end_ns"])
    return done / run["seconds"]
