"""Mean of the tracer's ``decode`` spans in the window: dispatch of the
decode step through the host's sync on its tokens."""


def read(run):
    d = [run.decode_spans[s] for s in run.steps if s in run.decode_spans]
    return 1e3 * sum(b - a for a, b in d) / len(d) if d else None
