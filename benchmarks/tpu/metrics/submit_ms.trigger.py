"""Engine admission: mean host time inside each ``submit`` call of the
window (validation, admission, enqueue), from the benchmark's own span
around the call."""


def read(run):
    if not len(run.submit_s):
        return None
    return float(run.submit_s.mean()) * 1e3
