"""Mean time (ms) of the program's own `serve.wave` span: one wave of
queries staged, featurized and answered by a replica."""


def read(view):
    waves = [s.duration for s in view.program_spans if s.name == "serve.wave"]
    return 1e3 * sum(waves) / len(waves) if waves else None
