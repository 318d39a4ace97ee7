"""setup_compile_s: seconds the program spent compiling before the traced
segment (tracing, lowering and the backend compile, which holds any read
of the persistent compilation cache), from the program's compile counter.
Set-up compiles all the run does before it: the window compiles
nothing."""
from bench import scopes


def read(r):
    got = scopes.of_run(r.summary)
    if got is None or got.start_ns is None:
        return None
    try:
        from repro import obs
    except ImportError:          # a program without its compile counter
        return None
    return obs.compile_seconds(obs.compile_totals(before_ns=got.start_ns))
