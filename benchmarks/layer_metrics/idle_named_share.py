"""Engine: share of the device's idle time in the traced span that the
program can name: seconds of the trace's idle gaps whose covering host
event is one of the program's annotations ("xllm.engine.<phase>",
"xllm.executor.<leaf>") over the seconds of all its idle gaps
(trace_reduce gives each gap the name of the host event that overlaps it
most). No trace, or a chip that never idled, gives nothing."""

PROGRAM = "xllm."


def compute(w):
    if w.trace is None:
        return None
    gaps = w.trace["idle_gaps"]
    total = sum(s for _, s in gaps)
    if not total:
        return None
    return 100.0 * sum(s for n, s in gaps if n.startswith(PROGRAM)) / total
