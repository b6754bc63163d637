"""Host time one engine iteration costs: the mean, over the traced window's
``engine.step`` spans, of a span's duration less the ``engine.wait`` time
inside it (the harvest's blocking transfer of sampled tokens)
(``serve/engine.py``). Once the device gets fast this is the step's floor."""

from bench import scopes


def read(run):
    spans = scopes.of(run).spans
    steps = scopes.span_events(run.trace, spans, "engine.step")
    if not steps:
        return None
    waits = scopes.span_events(run.trace, spans, "engine.wait")
    busy = 0.0
    for st in steps:
        busy += st.dur - sum(max(0.0, min(w.end, st.end) - max(w.start, st.start))
                             for w in waits)
    return 1e3 * busy / len(steps)
