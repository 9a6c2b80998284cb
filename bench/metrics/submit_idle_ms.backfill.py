"""Mean milliseconds per submit in which the device is idle inside the
``bench.submit`` span (host-to-device staging of the chunk and the host's
syncs), averaged over the cell's chips."""


def read(run):
    if run.trace is None or not run.calls_of("submit", decisions=False):
        return None
    submits = run.trace.spans_named("bench.submit")
    if not submits:
        return None
    idle = run.trace.idle_by_span(*run.trace_window()).get("bench.submit", 0.0)
    return idle / len(submits) / 1e6
