"""Images served per second: every request finished by a step that began
inside the window, over the time from the window's open to the end of
the last such step."""


def read(run):
    rec = run.record
    if not rec.steps or rec.end <= rec.t0:
        return None
    n = sum(1 for r in rec.requests
            if r.ok and rec.steps[r.step].start < rec.close)
    return n / (rec.end - rec.t0)
